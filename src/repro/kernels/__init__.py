"""Pallas TPU kernels for the framework's compute hot-spots.

* ``log_compress``  -- the ReCXL log-dump compressor (delta + blockwise
  int8/int4): the TPU-native analogue of the paper's gzip-9 stage.
* ``flash_attn``    -- blocked online-softmax GQA attention (the memory
  hot-spot of 8/10 assigned archs at 32k context).
* ``ssd_scan``      -- Mamba-2 SSD chunked scan in matmul form.

Each kernel ships ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd wrapper with a pure-jnp fallback) and ``ref.py`` (the oracle the
tests sweep against). The tests run them with ``interpret=True`` on the
CPU; none has been compiled for or run on a TPU. They serve the
training-side stack only: no simulator path calls them (the bank scan
is the XLA tile program of ``repro.core.engine``).
"""
