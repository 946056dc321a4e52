# Pallas TPU kernels for the compute hot spots:
#   flash_attention — serving/training attention (blocked online softmax,
#                     sliding window + logit softcap variants)
#   groupby_agg     — columnar group-by aggregation (the paper's
#                     usd_by_country hot spot; one-hot MXU reduction)
#   filter_compact  — predicate compaction (the paper's euro_selection hot
#                     spot; two-pass count + permute, no atomics)
# ops.py = jit'd wrappers (compiled on TPU, interpret mode on the CPU
#          platform, an error anywhere else);
# ref.py = pure-jnp oracles (the correctness contract for tests).
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
