"""Device-mesh parallelism for the storage data plane.

Maps Ceph's parallelism strategies (SURVEY.md §2.9) onto a
``jax.sharding.Mesh``:

- column-split encode (many objects/stripes laid side by side along
  the column dimension, one equal block per device, no collective) —
  the analogue of Ceph's per-PG sharded op queues and
  ``ParallelPGMapper`` thread fan-out: a GF(2^8) matrix product is
  independent column by column, so one compiled launch serves a whole
  window of ops on every chip at once.
"""

from ceph_tpu.parallel.decode_batcher import DecodeAggregator  # noqa: F401
from ceph_tpu.parallel.encode_farm import mesh_encode_cols  # noqa: F401
