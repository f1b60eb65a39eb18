# ctlint fixture: the disciplined twin of device_bad.py — bucketed
# dims, no unregistered jit site, sync outside the lock.
import threading

import jax
import jax.numpy as jnp

from ceph_tpu.ops.rs_kernels import gf_bitmatmul
from ceph_tpu.parallel.batcher import pow2_bucket

_dispatch_lock = threading.Lock()


def dispatch(bits, data):
    w = pow2_bucket(len(data))
    out = gf_bitmatmul(bits, jnp.zeros((1, 4, w), jnp.uint8))
    jax.block_until_ready(out)
    with _dispatch_lock:
        pass  # bookkeeping only under the lock
    return out
