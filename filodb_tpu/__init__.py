"""filodb_tpu — a TPU-native, Prometheus-compatible, in-memory time-series database.

A ground-up JAX/XLA re-design with the capabilities of FiloDB (reference:
filodb.coordinator / filodb.core / filodb.memory / filodb.query Scala modules):
columnar compressed storage, PromQL distributed query execution, sharded ingestion
with checkpointed recovery, durable persistence, downsampling, HTTP API.

See ARCHITECTURE.md for the design mapping.
"""

__version__ = "0.1.0"

# Epoch-millisecond timestamps are int64 end-to-end (device searchsorted included),
# so 64-bit mode is required. All library arrays specify dtypes explicitly; value
# columns stay f32 on device unless a store is configured for f64 parity runs.
import jax as _jax  # noqa: E402

_jax.config.update("jax_enable_x64", True)

# Every matmul in the query path multiplies sample values by 0/1 weights (band
# windows, one-hot first/last-sample selects, group folds): it must be exact in
# f32. XLA's default on the TPU runs an f32 dot as ONE bf16 pass — 8 bits of
# mantissa. First seen on the chip (PR 22): a raw selector read back 92160 for a
# stored 92181. The CPU backend is exact either way, so no CPU test could show it.
# The fused tiers spell their passes out (ops/fusedgrid.dot_exact01) and do not
# lean on this; the composed paths do.
_jax.config.update("jax_default_matmul_precision", "highest")
