# Pallas TPU kernels with a pure-jnp oracle each (ref.py):
#   lsh_hash        - grid-LSH bucket keys (the paper's per-update hashing)
#   pairwise_dist   - eps-neighbour counting (exact-DBSCAN baseline)
#   flash_attention - blocked online-softmax attention (LM substrate)
# The index calls its device programs through repro.kernels.ops, where the
# platform picks the kernel or the reference; submodules are importable
# directly (their tests pass interpret=True to run the kernels on CPU).
from . import ops, ref  # noqa: F401
