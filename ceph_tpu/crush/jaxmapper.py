"""Batched CRUSH placement engine — jit/vmap over placement seeds.

The TPU twin of the scalar rule interpreter (ceph_tpu/crush/mapper.py,
itself a bit-exact twin of reference src/crush/mapper.c): one compiled
XLA program maps a whole batch of placement seeds (pps values — every PG
of a pool at once) through TAKE/CHOOSE/EMIT rule programs.  This is the
engine behind the whole-cluster remap (ceph_tpu/osd/remap.py), the
batched analogue of the reference's thread-pooled ParallelPGMapper
(src/osd/OSDMapMapping.h:18-114).

Design notes (SURVEY.md §7 hard-part 4):

- The reference's rejection-retry control flow (crush_choose_firstn
  mapper.c:441-629, crush_choose_indep mapper.c:636-824) is
  data-dependent, so it is expressed as masked ``lax.while_loop`` state
  machines with the same bounded trip counts the C code has
  (choose_total_tries); ``vmap`` batches the machines over seeds.
- straw2 draws (mapper.c:315-365) need 64-bit fixed-point: the module
  runs its jitted programs under ``jax.enable_x64`` and is
  explicit about dtypes so the rest of the framework stays in default
  32-bit mode.
- The map compiles to dense padded arrays (items/weights/child tables);
  bucket descent becomes gathers + argmax, exactly mirroring the scalar
  semantics including first-index-wins tie breaking.

Supported surface (validated at compile; callers fall back to the
scalar mapper otherwise): straw2 buckets, rjenkins1 hash,
choose_local_fallback_tries == 0 (the modern "jewel+" tunable profiles —
the fallback path needs the stateful uniform-bucket permutation cache,
which is inherently sequential).  All rule step kinds, chooseleaf
recursion, vary_r/stable tunables, device classes, choose_args
weight-set overrides and reweights are implemented.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ceph_tpu.crush._ln_tables import LL_TBL, RH_LH_TBL
from ceph_tpu.crush.types import (
    CRUSH_HASH_RJENKINS1,
    CRUSH_ITEM_NONE,
    CRUSH_ITEM_UNDEF,
    BucketAlg,
    ChooseArg,
    CrushMap,
    Rule,
    RuleOp,
)

# while-loop statuses
_RUN, _PLACED, _SKIP = 0, 1, 2
# indep descent outcomes
_OUT_BREAK, _OUT_PLACE, _OUT_NONE = 0, 1, 2


class UnsupportedMap(NotImplementedError):
    """Map or rule uses a feature outside the batched engine's surface."""


@dataclasses.dataclass
class CompiledCrush:
    """Dense-array form of a CrushMap (+ one choose_args set)."""

    items: np.ndarray     # [NB, M] int32, padded with 0
    child: np.ndarray     # [NB, M] int32: dense idx of sub-bucket, -1 if device/unknown
    argids: np.ndarray    # [NB, M] int32: choose_args ids override (default items)
    weights: np.ndarray   # [NB, P, M] int64: per-position weights (16.16)
    npos: np.ndarray      # [NB] int32: valid weight positions per bucket
    size: np.ndarray      # [NB] int32
    btype: np.ndarray     # [NB] int32
    idx_of_arr: np.ndarray  # [K] int32: (-1 - bucket_id) -> dense idx, -1 unknown
    idx_of: dict          # bucket id -> dense idx
    max_devices: int
    max_depth: int
    tunables: object
    rules: dict
    device_classes: dict


def compile_map(
    cmap: CrushMap, choose_args: dict[int, ChooseArg] | None = None
) -> CompiledCrush:
    """Flatten a CrushMap into gather-friendly arrays.

    ``choose_args`` (balancer weight-set overrides) are baked in; pass a
    different set to get a different compiled map, mirroring how the
    reference snapshots choose_args per crush_do_rule call
    (mapper.c:290-307).
    """
    # first-compile latency on a cold process is the remap path's whole
    # startup cost (193 s measured on the chip for the 10k-PG map):
    # persist XLA executables across processes
    from ceph_tpu.ops.compile_cache import ensure_persistent_cache

    ensure_persistent_cache()
    ids = sorted(cmap.buckets.keys(), reverse=True)  # -1, -2, ...
    for bid in ids:
        b = cmap.buckets[bid]
        if b.alg != BucketAlg.STRAW2:
            raise UnsupportedMap(f"bucket {bid}: alg {b.alg!r} not batched")
        if b.hash != CRUSH_HASH_RJENKINS1:
            raise UnsupportedMap(f"bucket {bid}: hash {b.hash}")
    nb = max(len(ids), 1)
    m = max((cmap.buckets[i].size for i in ids), default=0)
    m = max(m, 1)
    idx_of = {bid: i for i, bid in enumerate(ids)}
    npos_all = 1
    if choose_args:
        for arg in choose_args.values():
            if arg.weight_set:
                npos_all = max(npos_all, len(arg.weight_set))

    items = np.zeros((nb, m), np.int32)
    child = np.full((nb, m), -1, np.int32)
    argids = np.zeros((nb, m), np.int32)
    weights = np.zeros((nb, npos_all, m), np.int64)
    npos = np.ones(nb, np.int32)
    size = np.zeros(nb, np.int32)
    btype = np.zeros(nb, np.int32)
    for bid in ids:
        i = idx_of[bid]
        b = cmap.buckets[bid]
        n = b.size
        size[i] = n
        btype[i] = b.type
        items[i, :n] = b.items
        argids[i, :n] = b.items
        for j, it in enumerate(b.items):
            if it < 0 and it in idx_of:
                child[i, j] = idx_of[it]
        weights[i, :, :n] = np.asarray(b.item_weights, np.int64)[None, :]
        arg = (choose_args or {}).get(bid)
        if arg is not None:
            if arg.ids is not None:
                argids[i, :n] = arg.ids
            if arg.weight_set:
                p = len(arg.weight_set)
                npos[i] = p
                for pi in range(p):
                    weights[i, pi, :n] = np.asarray(arg.weight_set[pi], np.int64)
                # positions beyond the set clamp to the last one
                for pi in range(p, npos_all):
                    weights[i, pi, :n] = weights[i, p - 1, :n]

    # depth bound for descent loops (and DAG check)
    depth: dict[int, int] = {}

    def _depth(bid: int, stack: frozenset) -> int:
        if bid in stack:
            raise UnsupportedMap("cycle in bucket graph")
        if bid in depth:
            return depth[bid]
        b = cmap.buckets[bid]
        d = 1 + max(
            (_depth(it, stack | {bid}) for it in b.items if it in cmap.buckets),
            default=0,
        )
        depth[bid] = d
        return d

    max_depth = max((_depth(bid, frozenset()) for bid in ids), default=1)

    k = max((-bid for bid in ids), default=0)
    idx_of_arr = np.full(max(k, 1), -1, np.int32)
    for bid in ids:
        idx_of_arr[-1 - bid] = idx_of[bid]

    return CompiledCrush(
        items=items, child=child, argids=argids, weights=weights,
        npos=npos, size=size, btype=btype,
        idx_of_arr=idx_of_arr, idx_of=idx_of,
        max_devices=cmap.max_devices, max_depth=max_depth,
        tunables=cmap.tunables, rules=cmap.rules,
        device_classes=dict(cmap.device_classes),
    )


def _jm_for(cc: CompiledCrush) -> "_Jm":
    """One shared device-side view per compiled map (the arrays are
    immutable after compile, so every rule mapper can reuse them)."""
    jm = getattr(cc, "_jm_cache", None)
    if jm is None:
        jm = _Jm(cc)
        cc._jm_cache = jm
    return jm


class _Jm:
    """Device-side (traced-constant) view of a CompiledCrush."""

    def __init__(self, cc: CompiledCrush):
        import jax.numpy as jnp

        self.items = jnp.asarray(cc.items)
        self.child = jnp.asarray(cc.child)
        self.argids = jnp.asarray(cc.argids)
        self.weights = jnp.asarray(cc.weights)
        self.npos = jnp.asarray(cc.npos)
        self.size = jnp.asarray(cc.size)
        self.btype = jnp.asarray(cc.btype)
        self.idx_of_arr = jnp.asarray(cc.idx_of_arr)
        self.rh_lh = jnp.asarray(RH_LH_TBL)
        self.ll = jnp.asarray(LL_TBL)
        self.nb = cc.items.shape[0]
        self.m = cc.items.shape[1]
        self.max_devices = cc.max_devices


def _crush_ln_j(jm: _Jm, u):
    """crush_ln (mapper.c:229-271) on int32 lanes -> int64.

    ``u`` is in [0, 0xffff] (the masked hash), so x = u+1 <= 0x10000 and
    bit_length fits a 17-term comparison sum (no clz needed)."""
    import jax.numpy as jnp

    x = u.astype(jnp.int32) + 1
    bl = jnp.zeros_like(x)
    for i in range(17):
        bl = bl + (x >= (1 << i)).astype(jnp.int32)
    cond = (x & 0x18000) == 0
    bits = jnp.int32(16) - bl
    x2 = jnp.where(cond, x << jnp.where(cond, bits, 0), x)
    iexpon = jnp.where(cond, jnp.int32(15) - bits, jnp.int32(15))
    index1 = (x2 >> 8) << 1
    rh = jm.rh_lh[index1 - 256]
    lh = jm.rh_lh[index1 - 255]
    # U64 product wraparound exactly as the C code's (x << 1) * RH path
    xl64 = (x2.astype(jnp.uint64) * rh.astype(jnp.uint64)) >> 48
    index2 = (xl64 & 0xFF).astype(jnp.int32)
    lh2 = (lh + jm.ll[index2]) >> 4
    return (iexpon.astype(jnp.int64) << 44) + lh2


def _straw2_choose(jm: _Jm, rew, bidx, x, r, pos):
    """bucket_straw2_choose (mapper.c:342-365): exponential-minimum draw
    per item, first-max wins.  Returns (item, child_idx)."""
    import jax.numpy as jnp

    from ceph_tpu.ops.hashing import crush_hash32_3_jax

    ids = jm.argids[bidx]                      # [M] int32
    p = jnp.clip(pos, 0, jm.npos[bidx] - 1)
    w = jm.weights[bidx, p]                    # [M] int64
    u = crush_hash32_3_jax(x, ids, r) & 0xFFFF
    ln = _crush_ln_j(jm, u)                    # int64, <= 2^48
    num = (jnp.int64(1) << 44) * 16 - ln       # 2^48 - ln  >= 0
    s64min = jnp.int64(-(2**63))
    draw = jnp.where(w > 0, -(num // jnp.maximum(w, 1)), s64min)
    in_range = jnp.arange(jm.m) < jm.size[bidx]
    draw = jnp.where(in_range, draw, s64min)
    hi = jnp.argmax(draw).astype(jnp.int32)
    return jm.items[bidx, hi], jm.child[bidx, hi]


def _is_out_j(jm: _Jm, rew, item, x):
    """Reweight rejection, mapper.c:405-419 (is_out)."""
    import jax.numpy as jnp

    from ceph_tpu.ops.hashing import crush_hash32_2_jax

    it = jnp.clip(item, 0, max(jm.max_devices - 1, 0))
    w = rew[it] if jm.max_devices else jnp.int32(0)
    h = crush_hash32_2_jax(x, item) & 0xFFFF
    return ~(w >= 0x10000) & ((w == 0) | (h >= w))


def _classify(jm: _Jm, item, cidx, type_):
    """Shared item classification: (is_dev, known, want, descend, skip)."""
    import jax.numpy as jnp

    too_big = item >= jm.max_devices
    is_dev = item >= 0
    known = is_dev | (cidx >= 0)
    ityp = jnp.where(
        is_dev | ~known, jnp.int32(0), jm.btype[jnp.clip(cidx, 0, jm.nb - 1)]
    )
    mismatch = ~known | (ityp != type_)
    want = ~too_big & ~mismatch
    descend = ~too_big & mismatch & known & ~is_dev
    skip = too_big | (mismatch & (is_dev | ~known))
    return is_dev, want, descend, skip


def _firstn_attempt(
    jm, rew, x, root, rep, parent_r, outpos, coll_buf, out2_buf, cap, *,
    type_, tries, local_retries, recurse, recurse_tries, vary_r, stable,
):
    """One replica attempt of crush_choose_firstn (mapper.c:441-629):
    the retry_descent/retry_bucket machinery as a while_loop state
    machine.  Returns (placed, item, leaf)."""
    import jax.numpy as jnp
    from jax import lax

    i32 = jnp.int32

    def cond(st):
        return st[0] == _RUN

    def body(st):
        status, in_idx, flocal, ftotal, item0, leaf0 = st
        size = jm.size[in_idx]
        r = rep + parent_r + ftotal
        item, cidx = _straw2_choose(jm, rew, in_idx, x, r, outpos)
        empty = size == 0
        is_dev, want, descend, skip_now = _classify(jm, item, cidx, type_)
        want = want & ~empty
        descend = descend & ~empty
        skip_now = skip_now & ~empty
        collide = want & jnp.any((jnp.arange(cap) < outpos) & (coll_buf == item))
        if recurse:
            sub_root = jnp.where(cidx >= 0, cidx, in_idx)
            sub_rep = i32(0) if stable else outpos
            sub_parent_r = (r >> (vary_r - 1)) if vary_r else i32(0)
            leaf_ok, leaf_item, _ = _firstn_attempt(
                jm, rew, x, sub_root, sub_rep, sub_parent_r, outpos,
                out2_buf, out2_buf, cap,
                type_=0, tries=recurse_tries, local_retries=local_retries,
                recurse=False, recurse_tries=0, vary_r=vary_r, stable=stable,
            )
            do_rec = want & ~collide & ~is_dev
            leaf_reject = do_rec & ~leaf_ok
            leaf_val = jnp.where(is_dev, item, leaf_item)
        else:
            leaf_reject = jnp.bool_(False)
            leaf_val = item
        if type_ == 0:
            out_rej = (
                want & ~collide & ~leaf_reject & is_dev
                & _is_out_j(jm, rew, item, x)
            )
        else:
            out_rej = jnp.bool_(False)
        fail = empty | (want & (collide | leaf_reject | out_rej))
        place = want & ~collide & ~leaf_reject & ~out_rej
        ftotal2 = ftotal + fail.astype(i32)
        flocal2 = flocal + fail.astype(i32)
        retry_same = fail & collide & (flocal2 <= local_retries)
        retry_root = fail & ~retry_same & (ftotal2 < tries)
        give_up = fail & ~retry_same & ~retry_root
        new_status = jnp.where(
            place, i32(_PLACED),
            jnp.where(skip_now | give_up, i32(_SKIP), i32(_RUN)),
        )
        new_in = jnp.where(
            descend, jnp.clip(cidx, 0, jm.nb - 1),
            jnp.where(retry_root, root, in_idx),
        )
        new_flocal = jnp.where(retry_root, i32(0), flocal2)
        return (
            new_status, new_in, new_flocal, ftotal2,
            jnp.where(place, item, item0), jnp.where(place, leaf_val, leaf0),
        )

    st0 = (i32(_RUN), root, i32(0), i32(0), i32(0), i32(0))
    st = lax.while_loop(cond, body, st0)
    return st[0] == _PLACED, st[4], st[5]


def _firstn_window(
    jm, rew, x, root, valid, numrep, out_size, cap, *,
    type_, tries, local_retries, recurse, recurse_tries, vary_r, stable,
):
    """One input bucket's output window of crush_choose_firstn: up to
    ``numrep`` attempts, placements bounded by ``out_size`` (avail).
    Returns (out[cap], out2[cap], n_placed)."""
    import jax.numpy as jnp

    i32 = jnp.int32
    undef = i32(CRUSH_ITEM_UNDEF)
    out = jnp.full((cap,), undef, jnp.int32)
    out2 = jnp.full((cap,), undef, jnp.int32)
    outpos = i32(0)
    for rep in range(numrep):
        active = valid & (outpos < out_size)
        placed, item, leaf = _firstn_attempt(
            jm, rew, x, root, i32(rep), i32(0), outpos, out, out2, cap,
            type_=type_, tries=tries, local_retries=local_retries,
            recurse=recurse, recurse_tries=recurse_tries,
            vary_r=vary_r, stable=stable,
        )
        commit = active & placed
        slot = jnp.arange(cap) == outpos
        out = jnp.where(slot & commit, item, out)
        out2 = jnp.where(slot & commit, leaf, out2)
        outpos = outpos + commit.astype(i32)
    return out, out2, outpos


def _indep_descent(
    jm, rew, x, root, rep, numrep, ftotal, parent_r, pos, out_buf, act, *,
    type_, recurse, recurse_tries,
):
    """One slot descent of crush_choose_indep (mapper.c:660-800 body).
    Returns (outcome, item, leaf)."""
    import jax.numpy as jnp
    from jax import lax

    i32 = jnp.int32

    def cond(st):
        return st[0] == _RUN

    def body(st):
        status, in_idx, oc0, item0, leaf0 = st
        size = jm.size[in_idx]
        r = rep + parent_r + numrep * ftotal
        item, cidx = _straw2_choose(jm, rew, in_idx, x, r, pos)
        empty = size == 0
        is_dev, want, descend, skip_now = _classify(jm, item, cidx, type_)
        want = want & ~empty
        descend = descend & ~empty
        place_none = skip_now & ~empty
        collide = want & jnp.any(act & (out_buf == item))
        if recurse:
            sub_root = jnp.where(cidx >= 0, cidx, in_idx)
            leaf_item = _indep_leaf(
                jm, rew, x, sub_root, rep, numrep, r,
                recurse_tries=recurse_tries,
            )
            do_rec = want & ~collide & ~is_dev
            leaf_fail = do_rec & (leaf_item == CRUSH_ITEM_NONE)
            leaf_val = jnp.where(is_dev, item, leaf_item)
        else:
            leaf_fail = jnp.bool_(False)
            leaf_val = item
        if type_ == 0:
            out_rej = (
                want & ~collide & ~leaf_fail & is_dev
                & _is_out_j(jm, rew, item, x)
            )
        else:
            out_rej = jnp.bool_(False)
        brk = empty | (want & (collide | leaf_fail | out_rej))
        place = want & ~collide & ~leaf_fail & ~out_rej
        outcome = jnp.where(
            place, i32(_OUT_PLACE), jnp.where(place_none, i32(_OUT_NONE), i32(_OUT_BREAK))
        )
        done = place | place_none | brk
        new_status = jnp.where(done, i32(1), i32(_RUN))
        new_in = jnp.where(descend, jnp.clip(cidx, 0, jm.nb - 1), in_idx)
        return (
            new_status, new_in,
            jnp.where(done, outcome, oc0),
            jnp.where(place, item, item0),
            jnp.where(place, leaf_val, leaf0),
        )

    st0 = (i32(_RUN), root, i32(_OUT_BREAK), i32(0), i32(0))
    st = lax.while_loop(cond, body, st0)
    return st[2], st[3], st[4]


def _indep_leaf(jm, rew, x, sub_root, rep, numrep, parent_r, *, recurse_tries):
    """The chooseleaf recursion of crush_choose_indep: a 1-slot indep
    window at type 0 with its own ftotal loop (tries=recurse_tries,
    choose-arg position = rep).  Returns the leaf item or NONE."""
    import jax.numpy as jnp
    from jax import lax

    i32 = jnp.int32
    undef = i32(CRUSH_ITEM_UNDEF)

    def cond(st):
        leaf, ftotal = st
        return (leaf == undef) & (ftotal < recurse_tries)

    def body(st):
        leaf, ftotal = st
        dummy = jnp.full((1,), undef, jnp.int32)
        oc, item, _ = _indep_descent(
            jm, rew, x, sub_root, rep, numrep, ftotal, parent_r, rep,
            dummy, jnp.zeros((1,), jnp.bool_),
            type_=0, recurse=False, recurse_tries=0,
        )
        leaf2 = jnp.where(
            oc == _OUT_PLACE, item,
            jnp.where(oc == _OUT_NONE, i32(CRUSH_ITEM_NONE), leaf),
        )
        return leaf2, ftotal + 1

    leaf, _ = lax.while_loop(cond, body, (undef, i32(0)))
    return jnp.where(leaf == undef, i32(CRUSH_ITEM_NONE), leaf)


def _indep_window(
    jm, rew, x, root, valid, numrep, left0, nw, *,
    type_, tries, recurse, recurse_tries,
):
    """crush_choose_indep over one window: positionally stable,
    breadth-first rounds bounded by ``tries``.  Returns (out[nw],
    out2[nw]) with NONE holes."""
    import jax.numpy as jnp
    from jax import lax

    i32 = jnp.int32
    undef = i32(CRUSH_ITEM_UNDEF)
    none = i32(CRUSH_ITEM_NONE)
    act = (jnp.arange(nw) < left0) & valid

    def cond(st):
        out, out2, ftotal = st
        return jnp.any(act & (out == undef)) & (ftotal < tries)

    def body(st):
        out, out2, ftotal = st
        for rep in range(nw):
            need = act[rep] & (out[rep] == undef)
            oc, item, leaf = _indep_descent(
                jm, rew, x, root, i32(rep), i32(numrep), ftotal, i32(0),
                i32(0), out, act,
                type_=type_, recurse=recurse, recurse_tries=recurse_tries,
            )
            place = need & (oc == _OUT_PLACE)
            pnone = need & (oc == _OUT_NONE)
            out = out.at[rep].set(
                jnp.where(place, item, jnp.where(pnone, none, out[rep]))
            )
            out2 = out2.at[rep].set(
                jnp.where(place, leaf, jnp.where(pnone, none, out2[rep]))
            )
        return out, out2, ftotal + 1

    out = jnp.full((nw,), undef, jnp.int32)
    out2 = jnp.full((nw,), undef, jnp.int32)
    out, out2, _ = lax.while_loop(cond, body, (out, out2, i32(0)))
    out = jnp.where(act & (out != undef), out, none)
    out2 = jnp.where(act & (out2 != undef), out2, none)
    return out, out2


def _msr_descend_j(jm, rew, x, bidx0, type_, r_value, pos, enabled):
    """crush_msr_descend twin (ceph_tpu/crush/mapper.py:433, reference
    mapper.c:1274) as a bounded while_loop over the dense bucket graph:
    draw at each level until a device or a bucket of ``type_``.
    Returns (item, child_idx) — item == CRUSH_ITEM_NONE encodes every
    map-integrity reject (empty bucket, dangling child, oversized
    device id), which the caller treats as a collision."""
    import jax.numpy as jnp
    from jax import lax

    i32 = jnp.int32
    none = i32(CRUSH_ITEM_NONE)

    def cond(st):
        depth, bidx, done, _it, _ci = st
        return ~done & (depth < jm.nb + 2)

    def body(st):
        depth, bidx, done, it, ci = st
        empty = jm.size[bidx] == 0
        item, cidx = _straw2_choose(jm, rew, bidx, x, r_value, pos)
        is_dev = item >= 0
        dev_ok = is_dev & (item < jm.max_devices)
        known = cidx >= 0
        btype = jm.btype[jnp.clip(cidx, 0, jm.nb - 1)]
        hit_type = ~is_dev & known & (btype == type_)
        stop = empty | is_dev | ~known | hit_type
        new_it = jnp.where(
            empty | (is_dev & ~dev_ok) | (~is_dev & ~known),
            none, item)
        return (depth + 1, jnp.where(stop, bidx, cidx), stop,
                jnp.where(stop, new_it, it),
                jnp.where(stop & hit_type, cidx, jnp.where(stop, i32(-1), ci)))

    _d, _b, _done, item, cidx = lax.while_loop(
        cond, body, (i32(0), bidx0, ~enabled, none, i32(-1)))
    return item, cidx


def _msr_window(idxs, lo, hi):
    return (idxs >= lo) & (idxs < hi)


def _msr_push_j(vec, s_lo, s_hi, cand, do):
    """crush_msr_push_used twin: set the first UNDEF slot in the
    stride window unless the candidate is already there.  Returns
    (vec, pushed)."""
    import jax.numpy as jnp

    idxs = jnp.arange(vec.shape[0], dtype=jnp.int32)
    win = _msr_window(idxs, s_lo, s_hi)
    present = jnp.any(win & (vec == cand))
    slots = win & (vec == CRUSH_ITEM_UNDEF)
    pos = jnp.argmax(slots).astype(jnp.int32)
    pushed = do & ~present & jnp.any(slots)
    return jnp.where(pushed, vec.at[pos].set(cand), vec), pushed


def _msr_pop_j(vec, s_lo, s_hi, cand, do):
    """crush_msr_pop_used twin: clear the last slot == cand in the
    stride window."""
    import jax.numpy as jnp

    rm = vec.shape[0]
    idxs = jnp.arange(rm, dtype=jnp.int32)
    eq = _msr_window(idxs, s_lo, s_hi) & (vec == cand)
    pos = (rm - 1 - jnp.argmax(eq[::-1])).astype(jnp.int32)
    return jnp.where(do & jnp.any(eq), vec.at[pos].set(CRUSH_ITEM_UNDEF), vec)


def _msr_valid_j(vec, seg_lo, seg_hi, s_lo, s_hi, cand):
    """crush_msr_valid_candidate twin: a candidate used elsewhere in
    the segment is invalid unless that use is inside our own stride."""
    import jax.numpy as jnp

    idxs = jnp.arange(vec.shape[0], dtype=jnp.int32)
    hit = _msr_window(idxs, seg_lo, seg_hi) & (vec == cand)
    return jnp.all(~hit | _msr_window(idxs, s_lo, s_hi))


def _append(acc, cnt, vals, n, rm):
    """result.extend(vals[:n]) with a dump slot at index rm."""
    import jax.numpy as jnp

    ln = vals.shape[0]
    idx = cnt + jnp.arange(ln)
    ok = (jnp.arange(ln) < n) & (idx < rm)
    tgt = jnp.where(ok, idx, rm)
    acc = acc.at[tgt].set(jnp.where(ok, vals, acc[rm]))
    cnt = jnp.minimum(cnt + jnp.maximum(n, 0), rm)
    return acc, cnt


class BatchedRuleMapper:
    """crush_do_rule over a batch of inputs, compiled once per
    (map, choose_args, rule, result_max)."""

    def __init__(self, cc: CompiledCrush, ruleno: int, result_max: int):
        if ruleno not in cc.rules:
            raise KeyError(f"no rule {ruleno}")
        self.cc = cc
        self.rule = cc.rules[ruleno]
        self.result_max = result_max
        self._validate()
        self._jitted = None

    def _validate(self):
        from ceph_tpu.crush.types import (
            RULE_TYPE_MSR_FIRSTN,
            RULE_TYPE_MSR_INDEP,
        )

        t = self.cc.tunables
        if t.choose_local_fallback_tries:
            raise UnsupportedMap("choose_local_fallback_tries > 0")
        if self.rule.rule_type in (RULE_TYPE_MSR_FIRSTN,
                                   RULE_TYPE_MSR_INDEP):
            # MSR rules take the dedicated lane (_msr_lane); only MSR
            # step kinds may appear (crush_msr_do_rule rejects others)
            for s in self.rule.steps:
                if s.op not in (
                    RuleOp.NOOP, RuleOp.TAKE, RuleOp.EMIT,
                    RuleOp.CHOOSE_MSR, RuleOp.SET_MSR_DESCENTS,
                    RuleOp.SET_MSR_COLLISION_TRIES,
                ):
                    raise UnsupportedMap(f"MSR rule op {s.op!r}")
            return
        for s in self.rule.steps:
            if s.op == RuleOp.SET_CHOOSE_LOCAL_FALLBACK_TRIES and s.arg1 > 0:
                raise UnsupportedMap("rule sets local_fallback_tries")
            if s.op in (RuleOp.CHOOSE_MSR, RuleOp.SET_MSR_DESCENTS,
                        RuleOp.SET_MSR_COLLISION_TRIES):
                raise UnsupportedMap(
                    "MSR step in a non-MSR rule")
            if s.op not in (
                RuleOp.NOOP, RuleOp.TAKE, RuleOp.EMIT,
                RuleOp.CHOOSE_FIRSTN, RuleOp.CHOOSE_INDEP,
                RuleOp.CHOOSELEAF_FIRSTN, RuleOp.CHOOSELEAF_INDEP,
                RuleOp.SET_CHOOSE_TRIES, RuleOp.SET_CHOOSELEAF_TRIES,
                RuleOp.SET_CHOOSE_LOCAL_TRIES,
                RuleOp.SET_CHOOSE_LOCAL_FALLBACK_TRIES,
                RuleOp.SET_CHOOSELEAF_VARY_R, RuleOp.SET_CHOOSELEAF_STABLE,
            ):
                raise UnsupportedMap(f"rule op {s.op!r}")

    # -- MSR lane (crush_msr_do_rule, mapper.c:1809) -------------------

    def _msr_lane(self, jm: _Jm, class_mask, x, rew):
        """Batched crush_msr_do_rule: the rule's stride tree is STATIC
        (stride boundaries derive from step arg1 counts and
        result_max), so the whole multi-step descent unrolls at trace
        time; the data-dependent parts — whole-descent retries
        (msr_descents), per-stride collision retries
        (msr_collision_tries) and the bucket-graph descent — run as
        bounded while_loops.  Statement-level twin of the scalar
        _msr_do_rule/_msr_choose (ceph_tpu/crush/mapper.py:519-680,
        reference mapper.c:1507,1809), pinned by the same golden
        vectors."""
        import jax.numpy as jnp
        from jax import lax

        from ceph_tpu.crush.mapper import (
            _msr_scan_config_steps,
            _msr_scan_next,
        )
        from ceph_tpu.crush.types import RULE_TYPE_MSR_FIRSTN

        cc = self.cc
        rm = self.result_max
        rule = self.rule
        i32 = jnp.int32
        none = i32(CRUSH_ITEM_NONE)
        undef = i32(CRUSH_ITEM_UNDEF)
        firstn = rule.rule_type == RULE_TYPE_MSR_FIRSTN

        if class_mask is not None:
            rew = jnp.where(class_mask, rew, 0)

        t = cc.tunables
        start_stepno, descents, collision_tries = _msr_scan_config_steps(rule)
        if descents is None:
            descents = t.msr_descents
        if collision_tries is None:
            collision_tries = t.msr_collision_tries

        out = jnp.full((rm + 1,), none, jnp.int32)
        returned = i32(0)

        def emit(out, returned, cand, position, do):
            pos = returned if firstn else i32(position)
            out = jnp.where(do, out.at[pos].set(cand), out)
            return out, returned + do

        def choose(vecs, out, returned, bidx, tryno, enabled,
                   lo, hi, total, stepno, seg_start_stepno, emit_stepno):
            """_msr_choose (mapper.c:1507): one level, strides
            unrolled.  ``total`` is the NOMINAL descendant count
            (stride boundaries use it; windows clip to ``hi`` exactly
            like the scalar's end_index).  The validity exclusion
            window is THIS invocation's [lo, hi) — recursed levels
            narrow it to the parent stride, exactly like the scalar's
            start_index/end_index threading.  Returns (vecs, out,
            returned, mapped)."""
            curstep = rule.steps[stepno]
            num_strides = curstep.arg1 if curstep.arg1 else rm
            if num_strides <= 0 or total % num_strides != 0:
                return vecs, out, returned, i32(0)  # malformed: skip
            length = total // num_strides
            if length <= 0:
                return vecs, out, returned, i32(0)
            level = stepno - seg_start_stepno
            leaf_level = emit_stepno - seg_start_stepno - 1
            is_leaf = curstep.arg2 == 0
            mapped = i32(0)
            undos: list = []
            idxs = jnp.arange(rm, dtype=jnp.int32)
            for sidx, s_lo in enumerate(range(lo, hi, length)):
                s_hi = min(s_lo + length, hi)
                filled = jnp.all(jnp.where(
                    _msr_window(idxs, s_lo, s_hi),
                    vecs[leaf_level] != undef, True))
                en = enabled & ~filled

                # collision loop: descend until a valid candidate
                def coll_cond(st):
                    lt, found, _c, _ci, _v = st
                    return ~found & (lt < collision_tries)

                def coll_body(st, _sidx=sidx, _s_lo=s_lo, _s_hi=s_hi,
                              _vec=vecs[level], _bidx=bidx):
                    lt, found, c, ci, v = st
                    r = (((tryno * rm) + _sidx) << 16) + lt
                    cand, cand_ci = _msr_descend_j(
                        jm, rew, x, _bidx, curstep.arg2, r,
                        i32(_sidx), jnp.bool_(True))
                    ok = cand != none
                    valid = ok & _msr_valid_j(
                        _vec, lo, hi, _s_lo, _s_hi, cand)
                    return (lt + 1, valid,
                            jnp.where(valid, cand, c),
                            jnp.where(valid, cand_ci, ci),
                            valid)

                _lt, found, cand, cand_ci, _v = lax.while_loop(
                    coll_cond, coll_body,
                    (i32(0), ~en, none, i32(-1), jnp.bool_(False)))
                found = found & en

                if is_leaf:
                    # leaf: stride_length must be 1 and this must be
                    # the last step (static malformed-rule guards)
                    if length != 1 or stepno + 1 != emit_stepno:
                        continue
                    do = found & ~_is_out_j(jm, rew, cand, x)
                    vec, pushed = _msr_push_j(
                        vecs[level], s_lo, s_hi, cand, do)
                    vecs = vecs[:level] + (vec,) + vecs[level + 1:]
                    out, returned = emit(out, returned, cand, s_lo, do)
                    mapped = mapped + do
                else:
                    if stepno + 1 >= emit_stepno:
                        continue  # malformed
                    en_child = found & (cand < 0)
                    vecs, out, returned, child_mapped = choose(
                        vecs, out, returned,
                        jnp.clip(cand_ci, 0, jm.nb - 1), tryno,
                        en_child, s_lo, s_hi, length, stepno + 1,
                        seg_start_stepno, emit_stepno)
                    vec, pushed = _msr_push_j(
                        vecs[level], s_lo, s_hi, cand, en_child)
                    vecs = vecs[:level] + (vec,) + vecs[level + 1:]
                    # a pushed interior candidate whose subtree mapped
                    # nothing is popped — but only AFTER every stride
                    # at this level ran (the scalar's undo array): the
                    # failed candidate must stay visible to later
                    # strides' validity checks within this pass
                    undos.append((s_lo, s_hi, cand,
                                  pushed & (child_mapped == 0)))
                    mapped = mapped + child_mapped
            for s_lo, s_hi, cand, flag in undos:
                vec = _msr_pop_j(vecs[level], s_lo, s_hi, cand, flag)
                vecs = vecs[:level] + (vec,) + vecs[level + 1:]
            return vecs, out, returned, mapped

        stepno = start_stepno
        start_index = 0
        while stepno < len(rule.steps):
            scan = _msr_scan_next(rule, rm, stepno)
            if scan is None:
                # invalid rule: "return whatever we have" (= none)
                return jnp.full((rm + 1,), none, jnp.int32), i32(0)
            total_children, emit_stepno = scan
            take_step = rule.steps[stepno]
            if take_step.arg1 >= 0:
                if stepno + 1 != emit_stepno:
                    return jnp.full((rm + 1,), none, jnp.int32), i32(0)
                # NB: the scalar twin does NOT advance start_index
                # after a raw-device take (mapper.py:639) — match it
                out, returned = emit(
                    out, returned, i32(take_step.arg1), start_index,
                    jnp.bool_(True))
            elif take_step.arg1 not in cc.idx_of:
                pass  # unknown root: nothing placed for this segment
            else:
                root = i32(cc.idx_of[take_step.arg1])
                seg_start = stepno + 1
                n_steps = emit_stepno - seg_start
                end_index = min(start_index + total_children, rm)
                vecs0 = tuple(
                    jnp.full((rm,), undef, jnp.int32)
                    for _ in range(n_steps))
                return_limit = returned + (end_index - start_index)

                def desc_cond(st):
                    tryno, _v, _o, ret = st
                    return (tryno < descents) & (ret < return_limit)

                def desc_body(st, _root=root, _seg=seg_start,
                              _emit=emit_stepno, _lo=start_index,
                              _hi=end_index, _tot=total_children):
                    tryno, vecs, out, ret = st
                    vecs, out, ret, _m = choose(
                        vecs, out, ret, _root, tryno, jnp.bool_(True),
                        _lo, _hi, _tot, _seg, _seg, _emit)
                    return (tryno + 1, vecs, out, ret)

                _t, _v, out, returned = lax.while_loop(
                    desc_cond, desc_body, (i32(0), vecs0, out, returned))
                start_index = end_index
            stepno = emit_stepno + 1

        if firstn:
            return out[:rm], returned
        return out[:rm], i32(rm)

    # -- trace-time interpreter (steps are static) --------------------

    def _lane(self, jm: _Jm, class_mask, x, rew):
        import jax.numpy as jnp

        from ceph_tpu.crush.types import (
            RULE_TYPE_MSR_FIRSTN,
            RULE_TYPE_MSR_INDEP,
        )

        if self.rule.rule_type in (RULE_TYPE_MSR_FIRSTN,
                                   RULE_TYPE_MSR_INDEP):
            return self._msr_lane(jm, class_mask, x, rew)

        cc = self.cc
        rm = self.result_max
        i32 = jnp.int32
        t = cc.tunables
        choose_tries = t.choose_total_tries + 1
        choose_leaf_tries = 0
        local_retries = t.choose_local_tries
        vary_r = t.chooseleaf_vary_r
        stable = t.chooseleaf_stable

        if class_mask is not None:
            rew = jnp.where(class_mask, rew, 0)

        res = jnp.full((rm + 1,), CRUSH_ITEM_NONE, jnp.int32)
        res_cnt = i32(0)
        w: tuple = ("empty",)

        for step in self.rule.steps:
            op = step.op
            if op == RuleOp.TAKE:
                ok = (0 <= step.arg1 < cc.max_devices) or step.arg1 in cc.idx_of
                w = ("static", step.arg1) if ok else ("empty",)
            elif op == RuleOp.SET_CHOOSE_TRIES:
                if step.arg1 > 0:
                    choose_tries = step.arg1
            elif op == RuleOp.SET_CHOOSELEAF_TRIES:
                if step.arg1 > 0:
                    choose_leaf_tries = step.arg1
            elif op == RuleOp.SET_CHOOSE_LOCAL_TRIES:
                if step.arg1 >= 0:
                    local_retries = step.arg1
            elif op == RuleOp.SET_CHOOSELEAF_VARY_R:
                if step.arg1 >= 0:
                    vary_r = step.arg1
            elif op == RuleOp.SET_CHOOSELEAF_STABLE:
                if step.arg1 >= 0:
                    stable = step.arg1
            elif op in (
                RuleOp.CHOOSE_FIRSTN, RuleOp.CHOOSELEAF_FIRSTN,
                RuleOp.CHOOSE_INDEP, RuleOp.CHOOSELEAF_INDEP,
            ):
                if w[0] == "empty":
                    continue
                firstn = op in (RuleOp.CHOOSE_FIRSTN, RuleOp.CHOOSELEAF_FIRSTN)
                leafy = op in (RuleOp.CHOOSELEAF_FIRSTN, RuleOp.CHOOSELEAF_INDEP)
                if firstn:
                    if choose_leaf_tries:
                        recurse_tries = choose_leaf_tries
                    elif t.chooseleaf_descend_once:
                        recurse_tries = 1
                    else:
                        recurse_tries = choose_tries
                else:
                    recurse_tries = choose_leaf_tries if choose_leaf_tries else 1

                # windows: (root_idx, valid) sources from w
                if w[0] == "static":
                    wi = w[1]
                    if wi >= 0 or wi not in cc.idx_of:
                        sources = []
                    else:
                        sources = [(i32(cc.idx_of[wi]), jnp.bool_(True))]
                else:
                    vals, cnt = w[1], w[2]
                    sources = []
                    for j in range(rm):
                        wi = vals[j]
                        key = jnp.clip(-1 - wi, 0, jm.idx_of_arr.shape[0] - 1)
                        cidx = jm.idx_of_arr[key]
                        valid = (j < cnt) & (wi < 0) & (cidx >= 0)
                        sources.append((jnp.clip(cidx, 0, jm.nb - 1), valid))

                o = jnp.full((rm + 1,), CRUSH_ITEM_NONE, jnp.int32)
                o_cnt = i32(0)
                for root, valid in sources:
                    numrep = step.arg1
                    if numrep <= 0:
                        numrep += rm
                        if numrep <= 0:
                            continue
                    avail = rm - o_cnt
                    nw = min(numrep, rm)
                    if firstn:
                        out, out2, n = _firstn_window(
                            jm, rew, x, root, valid, numrep,
                            jnp.minimum(avail, numrep), nw,
                            type_=step.arg2, tries=choose_tries,
                            local_retries=local_retries, recurse=leafy,
                            recurse_tries=recurse_tries,
                            vary_r=vary_r, stable=stable,
                        )
                    else:
                        left0 = jnp.clip(jnp.minimum(avail, numrep), 0, nw)
                        out, out2 = _indep_window(
                            jm, rew, x, root, valid, numrep, left0, nw,
                            type_=step.arg2, tries=choose_tries,
                            recurse=leafy, recurse_tries=recurse_tries,
                        )
                        n = left0
                    vals_use = out2 if leafy else out
                    n = jnp.where(valid, n, 0)
                    o, o_cnt = _append(o, o_cnt, vals_use, n, rm)
                w = ("traced", o[:rm], o_cnt)
            elif op == RuleOp.EMIT:
                if w[0] == "static":
                    res, res_cnt = _append(
                        res, res_cnt,
                        jnp.full((1,), w[1], jnp.int32), i32(1), rm,
                    )
                elif w[0] == "traced":
                    res, res_cnt = _append(res, res_cnt, w[1], w[2], rm)
                w = ("empty",)
        return res[:rm], res_cnt

    def _build(self):
        import jax
        import jax.numpy as jnp

        cc = self.cc
        jm = _jm_for(cc)
        if self.rule.device_class is not None:
            mask = np.zeros(max(cc.max_devices, 1), bool)
            for osd, cls in cc.device_classes.items():
                if cls == self.rule.device_class and osd < cc.max_devices:
                    mask[osd] = True
            class_mask = jnp.asarray(mask)
        else:
            class_mask = None

        def lane(x, rew):
            return self._lane(jm, class_mask, x, rew)

        return jax.jit(jax.vmap(lane, in_axes=(0, None)))

    def __call__(self, xs, reweights=None):
        """Map a batch of placement seeds.

        Returns (vals [B, result_max] int32 with CRUSH_ITEM_NONE
        padding/holes, counts [B] int32): per lane the rule result is
        vals[i, :counts[i]], exactly crush_do_rule's output."""
        import jax

        cc = self.cc
        xs = np.asarray(xs, np.uint32).astype(np.int32)
        if reweights is None:
            rew = np.full(max(cc.max_devices, 1), 0x10000, np.int32)
        else:
            rew = np.zeros(max(cc.max_devices, 1), np.int32)
            rw = np.asarray(reweights, np.int64)
            rew[: len(rw)] = rw[: len(rew)]
        with jax.enable_x64(True):
            if self._jitted is None:
                self._jitted = self._build()
            # explicit transfer discipline (ctlint device-host-sink):
            # the two inputs ride one device_put each and the mapping
            # result comes back in ONE device_get — the by-design host
            # exit (placements feed the host-side OSDMap/peering code)
            vals, cnt = self._jitted(
                jax.device_put(xs), jax.device_put(rew))
            return jax.device_get(vals), jax.device_get(cnt)
