"""Pallas TPU kernels for the paper's compute hot-spots.

  sinnamon_score — Algorithm 6 scoring (tile-resident sketch + bitmask)
  csr_score      — exact padded-CSR scan (LinScan / Algorithm 7 rerank)
  embed_bag      — EmbeddingBag gather-reduce (recsys substrate)

Each kernel has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py.
Validated in interpret mode on CPU.  On a TPU the serving path runs
sinnamon_score compiled (its compile for v5e is a test:
tests/test_tpu_compile.py); csr_score and embed_bag are reached only by
their interpret-mode tests.
"""
