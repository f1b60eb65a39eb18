"""EC <-> OSD glue: stripe math, batched stripe encode/decode, HashInfo.

Behavioral twin of reference src/osd/ECUtil.{h,cc}:

- :class:`StripeInfo`  = ``ECUtil::stripe_info_t`` (ECUtil.h:27-81);
- :func:`encode`       = ``ECUtil::encode`` (ECUtil.cc:123-162);
- :func:`decode_concat`= ``ECUtil::decode`` concat form (ECUtil.cc:12-48);
- :func:`decode_shards`= ``ECUtil::decode`` per-target-shard form with
  CLAY sub-chunk minimums honored (ECUtil.cc:50-121);
- :class:`HashInfo`    = ``ECUtil::HashInfo`` cumulative per-shard
  crc32c chains (ECUtil.cc:164-248).

TPU-first difference: where the reference loops ``encode``/``decode``
per stripe_width slice, matrix codes here assemble the whole multi-
stripe payload into one row-space operand and run ONE GF matmul (on
device above the plugin's batch threshold).  Shard layouts are
bit-identical to the reference's per-stripe loop because shard i's
payload is simply the concatenation of stripe-chunk i over stripes.
"""

from __future__ import annotations

import errno
from typing import Mapping

import numpy as np

from ceph_tpu.ec.interface import ECError, ErasureCodeInterface
from ceph_tpu.ec.plugins.matrix_base import MatrixErasureCode
from ceph_tpu.native import crc32c
from ceph_tpu.osd.pgutil import STRIPE_UNIT


class StripeInfo:
    """stripe_info_t (ECUtil.h:27-81): stripe_width = k * chunk_size."""

    def __init__(self, stripe_size: int, stripe_width: int):
        assert stripe_width % stripe_size == 0, (stripe_width, stripe_size)
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // stripe_size

    def logical_offset_is_stripe_aligned(self, logical: int) -> bool:
        return logical % self.stripe_width == 0

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return (
            (offset + self.stripe_width - 1) // self.stripe_width
        ) * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset + self.stripe_width - rem if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def aligned_offset_len_to_chunk(self, off: int, length: int) -> tuple[int, int]:
        return (
            self.aligned_logical_offset_to_chunk_offset(off),
            self.aligned_logical_offset_to_chunk_offset(length),
        )

    def offset_len_to_stripe_bounds(self, off: int, length: int) -> tuple[int, int]:
        start = self.logical_to_prev_stripe_offset(off)
        return start, self.logical_to_next_stripe_offset((off - start) + length)


def stripe_unit_of(profile: Mapping[str, str]) -> int:
    """The pool's stripe unit: its erasure-code profile's ``stripe_unit``
    (upstream's key), else osd_pool_erasure_code_stripe_unit's 4096."""
    return int(profile.get("stripe_unit", STRIPE_UNIT))


def stripe_info(ec_impl: ErasureCodeInterface) -> StripeInfo:
    """A pool's stripe geometry from its code and its profile
    (OSDMonitor::prepare_pool_stripe_width)."""
    k = ec_impl.get_data_chunk_count()
    unit = stripe_unit_of(ec_impl.get_profile())
    return StripeInfo(k, ec_impl.get_chunk_size(unit * k) * k)


def check_stripe_unit(ec_impl: ErasureCodeInterface) -> None:
    """A profile's ``stripe_unit`` must be a chunk size the plugin
    would give unpadded (the check of ``osd erasure-code-profile
    set``): a unit the plugin pads is a pool whose stripes are not the
    width the profile states."""
    if "stripe_unit" not in ec_impl.get_profile():
        return
    unit = stripe_unit_of(ec_impl.get_profile())
    chunk = ec_impl.get_chunk_size(unit * ec_impl.get_data_chunk_count()) \
        if unit > 0 else -1
    if chunk != unit:
        raise ECError(errno.EINVAL, (
            f"stripe_unit {unit} does not match ec profile alignment. "
            f"Would be padded to {chunk}"))


def row_view(row: np.ndarray) -> memoryview:
    """A shard row of an encode or decode result, handed on as a view
    of its bytes and not as a copy of them: to the wire it is a
    frame's data segment, to the local store the buffer of its pwrite.
    Nobody writes to such a result again, and nobody may while a full
    socket or a queued commit still holds the view."""
    return memoryview(np.ascontiguousarray(row, dtype=np.uint8))


def encode(
    sinfo: StripeInfo,
    ec_impl: ErasureCodeInterface,
    data: bytes | np.ndarray,
    want: set[int] | None = None,
) -> dict[int, np.ndarray]:
    """ECUtil::encode (ECUtil.cc:123-162): stripe-aligned logical bytes
    -> per-shard chunk payloads.  Matrix codes take the batched one-
    matmul path; other plugins fall back to the per-stripe loop."""
    arr = (
        np.asarray(data, dtype=np.uint8).reshape(-1)
        if isinstance(data, np.ndarray)
        else np.frombuffer(data, dtype=np.uint8)    # bytes or a view
    )
    sw, cs = sinfo.stripe_width, sinfo.chunk_size
    if arr.nbytes % sw:
        raise ECError(errno.EINVAL, f"logical size {arr.nbytes} not stripe aligned")
    n_chunks = ec_impl.get_chunk_count()
    k = ec_impl.get_data_chunk_count()
    if want is None:
        want = set(range(n_chunks))
    if arr.nbytes == 0:
        return {}
    ns = arr.nbytes // sw

    if isinstance(ec_impl, MatrixErasureCode):
        # shard i of the per-stripe loop == concat over stripes of
        # stripe-chunk i: a transpose of (ns, k, cs).  encode_chunks
        # operates on payloads of any superpacket multiple, so the
        # whole multi-stripe batch is one matmul.
        data_shards = np.ascontiguousarray(
            arr.reshape(ns, k, cs).transpose(1, 0, 2).reshape(k, ns * cs)
        )
        encoded: dict[int, np.ndarray] = {}
        for i in range(k):
            encoded[ec_impl.chunk_index(i)] = data_shards[i]
        for j in range(k, n_chunks):
            encoded[ec_impl.chunk_index(j)] = np.zeros(ns * cs, dtype=np.uint8)
        ec_impl.encode_chunks(set(range(n_chunks)), encoded)
        return {s: c for s, c in encoded.items() if s in want}

    out: dict[int, list] = {}
    for s in range(ns):
        encoded = ec_impl.encode(set(range(n_chunks)), arr[s * sw : (s + 1) * sw])
        for shard, chunk in encoded.items():
            assert len(chunk) == cs
            out.setdefault(shard, []).append(chunk)
    return {
        s: np.concatenate(bufs) for s, bufs in out.items() if s in want
    }


# -- async twins: the encode-farm data path ---------------------------------
#
# The OSD daemon's EC write/read/recovery paths call these instead of the
# sync functions; when an EncodeService with a live device mesh is
# attached (ceph_tpu/parallel/encode_service.py), the GF matmul of each
# op is coalesced with concurrent ops into one sharded farm dispatch —
# the production form of the ECSubWrite fan-out seam (reference
# src/osd/ECCommon.cc:749, SURVEY.md §2.9).  Every gate failure falls
# back to the sync single-device path, so behavior is identical; a
# flushed group too small for a launch is answered on the host by the
# service itself.


def _service_takes(service) -> bool:
    """An active service is filed every request, whatever its size:
    whether a launch is worth its cost is decided per flushed group, by
    what the group carries (parallel/batcher.py), not per caller."""
    return service is not None and service.active()


def _farm_ready(service, ec_impl) -> bool:
    return (
        _service_takes(service)
        and isinstance(ec_impl, MatrixErasureCode)
        and ec_impl.rows_per_chunk == 1
    )


# A linear code whose plugin gives its matrices (``encode_matrix`` and
# ``linear_plan``: CLAY over sub-chunk rows, LRC's stacked layers over
# whole chunks) takes the same engines with a reshaped operand: a chunk
# payload of ns stripes is (ns, alpha, sc) bytes, and sub-chunk z of
# every stripe lies side by side in row z (alpha = 1: the payload is the
# one row).  CLAY's single-chunk repair from packed sub-chunk reads is
# its ``repair_matrix``.

def _is_linear_code(ec_impl) -> bool:
    return hasattr(ec_impl, "linear_plan")


def _to_subchunk_rows(payload: np.ndarray, n_sub: int, sc: int) -> np.ndarray:
    """(ns * n_sub * sc,) stripe-major -> (n_sub, ns * sc)."""
    return payload.reshape(-1, n_sub, sc).transpose(1, 0, 2).reshape(
        n_sub, -1)


def _from_subchunk_rows(rows: np.ndarray, sc: int) -> np.ndarray:
    """(n_sub, ns * sc) -> the stripe-major (ns * n_sub * sc,) payload."""
    n_sub = rows.shape[0]
    return np.ascontiguousarray(
        rows.reshape(n_sub, -1, sc).transpose(1, 0, 2)).reshape(-1)


def _rows_of(to_decode, helpers, n_sub: int, sc: int) -> np.ndarray:
    """The helpers' payloads as one operand, helper after helper."""
    return np.concatenate([
        _to_subchunk_rows(np.asarray(to_decode[h]).reshape(-1), n_sub, sc)
        for h in helpers])


async def _encode_linear_async(sinfo, ec_impl, arr, want, service):
    k = ec_impl.get_data_chunk_count()
    m = ec_impl.get_chunk_count() - k
    cs = sinfo.chunk_size
    alpha = ec_impl.get_sub_chunk_count()
    sc = cs // alpha
    data = arr.reshape(-1, k, cs).transpose(1, 0, 2)        # (k, ns, cs)
    rows = np.ascontiguousarray(
        data.reshape(k, -1, alpha, sc).transpose(0, 2, 1, 3)
    ).reshape(k * alpha, -1)
    parity = await service.apply(ec_impl.encode_matrix(), rows)
    out = {ec_impl.chunk_index(i): np.ascontiguousarray(data[i]).reshape(-1)
           for i in range(k)}
    for j in range(m):
        out[ec_impl.chunk_index(k + j)] = _from_subchunk_rows(
            parity[j * alpha:(j + 1) * alpha], sc)
    if want is not None:
        out = {s: c for s, c in out.items() if s in want}
    return out


async def _repair_linear_async(
    sinfo, ec_impl, to_decode, need, aggregator, *, packed: bool,
    how: dict | None,
) -> dict[int, np.ndarray] | None:
    """The chunks ``need`` rebuilt from their helpers' reads as one
    matrix filed with the aggregator: a vector code's regenerating
    repair of one chunk from packed sub-chunk reads (``repair_matrix``),
    or a scalar linear code's from the whole chunks ``linear_plan``
    names (an LRC chunk from its local group).  ``how`` gets the
    launch's ``kind`` and the ``helpers`` it read.  None = the caller
    takes another path."""
    if aggregator is None or not _is_linear_code(ec_impl):
        return None
    alpha = ec_impl.get_sub_chunk_count()
    if packed:
        if len(need) != 1:
            return None
        rebuilt = sorted(need)
        M = ec_impl.repair_matrix(rebuilt[0])
        if M is None or set(to_decode) != set(
                ec_impl.repair_helpers(rebuilt[0])):
            return None
        helpers, kind = sorted(to_decode), ec_impl.REPAIR_KIND
    elif alpha == 1:
        try:
            helpers, M = ec_impl.linear_plan(set(need), set(to_decode))
        except ECError:
            return None         # the layers cannot: the host says so
        if M is None:
            return None
        rebuilt = sorted(set(need) - set(helpers))
        kind = ec_impl.REPAIR_KIND \
            if len(helpers) < ec_impl.get_data_chunk_count() else \
            "decode_batch"
    else:
        return None     # a vector code's whole-chunk decode: the host's
    sc = sinfo.chunk_size // alpha
    out = await aggregator.apply(
        M, _rows_of(to_decode, helpers, M.shape[1] // len(helpers), sc),
        kind=kind, lost_node=rebuilt[0])
    if how is not None:
        how.update(kind=kind, helpers=len(helpers))
    return {c: _from_subchunk_rows(out[i * alpha:(i + 1) * alpha], sc)
            for i, c in enumerate(rebuilt)}


async def encode_async(
    sinfo: StripeInfo,
    ec_impl: ErasureCodeInterface,
    data: bytes | np.ndarray,
    want: set[int] | None = None,
    *,
    service=None,
) -> dict[int, np.ndarray]:
    """:func:`encode` routed through the encode farm when available."""
    arr = (
        np.asarray(data, dtype=np.uint8).reshape(-1)
        if isinstance(data, np.ndarray)
        else np.frombuffer(data, dtype=np.uint8)    # bytes or a view
    )
    linear = _service_takes(service) and _is_linear_code(ec_impl)
    if not linear and not _farm_ready(service, ec_impl):
        return encode(sinfo, ec_impl, arr, want)
    sw, cs = sinfo.stripe_width, sinfo.chunk_size
    if arr.nbytes % sw:
        raise ECError(errno.EINVAL, f"logical size {arr.nbytes} not stripe aligned")
    if arr.nbytes == 0:
        return {}
    if linear:
        return await _encode_linear_async(sinfo, ec_impl, arr, want, service)
    k, m = ec_impl.get_data_chunk_count(), ec_impl.get_chunk_count() - ec_impl.get_data_chunk_count()
    ns = arr.nbytes // sw
    data_shards = np.ascontiguousarray(
        arr.reshape(ns, k, cs).transpose(1, 0, 2).reshape(k, ns * cs)
    )
    parity = await service.apply(ec_impl.coding_matrix, data_shards)
    out = {ec_impl.chunk_index(i): data_shards[i] for i in range(k)}
    for j in range(m):
        out[ec_impl.chunk_index(k + j)] = parity[j]
    if want is not None:
        out = {s: c for s, c in out.items() if s in want}
    return out


async def _decode_linear_async(
    sinfo, ec_impl, to_decode, data, service
) -> dict[int, np.ndarray] | None:
    """A read's data chunks (``data``: their positions), those it lost
    rebuilt from the fetched chunks as ONE matrix filed with the encode
    service (``linear_plan``); None = the caller takes the host path."""
    try:
        fetched, D = ec_impl.linear_plan(set(data), set(to_decode))
    except ECError:
        return None
    out = {c: np.asarray(to_decode[c]).reshape(-1)
           for c in data if c in to_decode}
    if D is None:
        return out
    if not _service_takes(service):
        return None
    alpha = ec_impl.get_sub_chunk_count()
    sc = sinfo.chunk_size // alpha
    rec = await service.apply(D, _rows_of(to_decode, fetched, alpha, sc),
                              kind=ec_impl.DECODE_KIND)
    for i, c in enumerate(sorted(set(data) - set(fetched))):
        out[c] = _from_subchunk_rows(rec[i * alpha:(i + 1) * alpha], sc)
    return out


async def decode_concat_async(
    sinfo: StripeInfo,
    ec_impl: ErasureCodeInterface,
    to_decode: Mapping[int, np.ndarray],
    *,
    service=None,
    how: dict | None = None,
) -> np.ndarray:
    """:func:`decode_concat` with the lost data chunks rebuilt on the
    device: a scalar code's by its decode matrix, a linear code's whose
    plugin gives its plan (CLAY over sub-chunk rows, LRC) as one matrix.
    ``how``, where given, is filled with what served it: ``path``
    (``"device"``, ``"host"`` or ``"none"``: every data chunk was
    fetched), ``lost`` (the positions of the data chunks rebuilt) and
    ``rebuilt_bytes``."""
    k = ec_impl.get_data_chunk_count()
    data = [ec_impl.chunk_index(i) for i in range(k)]
    total = len(next(iter(to_decode.values()))) if to_decode else 0
    lost = [c for c in data if c not in to_decode] if total else []
    if not total:
        rec = None
    elif _is_linear_code(ec_impl):
        rec = await _decode_linear_async(
            sinfo, ec_impl, to_decode, data, service)
    else:
        rec = await _decode_chunks_async(
            sinfo, ec_impl, to_decode, range(k), service=service)
        if rec is not None:
            rec = {ec_impl.chunk_index(c): v for c, v in rec.items()}
    if how is not None:
        how.update(path="none" if not lost else
                   "host" if rec is None else "device",
                   lost=lost, rebuilt_bytes=len(lost) * total)
    if rec is None:
        return decode_concat(sinfo, ec_impl, to_decode)
    cs, sw = sinfo.chunk_size, sinfo.stripe_width
    ns = total // cs
    stacked = np.stack([rec[c].reshape(ns, cs) for c in data], axis=1)
    return np.ascontiguousarray(stacked.reshape(ns * sw))


async def decode_shards_async(
    sinfo: StripeInfo,
    ec_impl: ErasureCodeInterface,
    to_decode: Mapping[int, np.ndarray],
    need: set[int],
    *,
    packed_repair: bool = False,
    service=None,
    aggregator=None,
    how: dict | None = None,
) -> dict[int, np.ndarray]:
    """:func:`decode_shards` with batched reconstruction (recovery
    path).  A linear code's repair (a vector code's packed single-chunk
    repair, a scalar linear code's from its plan's helpers) is one
    matrix like a scalar code's decode, and says so in ``how``
    (:func:`_repair_linear_async`); a vector code's other decodes
    (several losses, full-chunk reads, d < k+m-1) stay on the host loop.

    ``aggregator`` (a parallel.decode_batcher.DecodeAggregator) takes
    precedence over the encode farm: per-object recovery decodes that
    share an erasure signature coalesce into fixed-shape batched
    launches — the repair-pipelining discipline — instead of one farm
    matmul per object."""
    if all(np.asarray(v).size for v in to_decode.values()):
        rec = await _repair_linear_async(
            sinfo, ec_impl, to_decode, need, aggregator,
            packed=packed_repair, how=how)
        if rec is not None:
            return rec
    if packed_repair or (
        not isinstance(ec_impl, MatrixErasureCode)
        or ec_impl.get_sub_chunk_count() != 1
    ):
        return decode_shards(sinfo, ec_impl, to_decode, need,
                             packed_repair=packed_repair)
    inv = {ec_impl.chunk_index(c): c for c in range(ec_impl.get_chunk_count())}
    want_chunks = [inv[s] for s in need]
    if aggregator is not None and to_decode:
        rec = await _decode_chunks_batched(
            ec_impl, to_decode, want_chunks, aggregator)
        if rec is not None:
            return {ec_impl.chunk_index(c): v for c, v in rec.items()}
    rec = await _decode_chunks_async(sinfo, ec_impl, to_decode,
                                     want_chunks, service=service)
    if rec is None:
        return decode_shards(sinfo, ec_impl, to_decode, need,
                             packed_repair=packed_repair)
    return {ec_impl.chunk_index(c): v for c, v in rec.items()}


async def _decode_chunks_batched(
    ec_impl, to_decode, want_chunks, aggregator
) -> dict[int, np.ndarray] | None:
    """decode_payloads with the matmul coalesced across concurrent
    recovery decodes by the aggregator; None = take another path."""
    want_chunks = list(want_chunks)
    erasures, survivors, need_rec, D = ec_impl.decode_plan(
        to_decode, want_chunks)
    rec_rows = None
    if need_rec:
        rows = ec_impl.decode_rows(to_decode, survivors)
        if rows.shape[1] == 0:
            return None
        # the rows of the chunks wanted alone: a repair that read k
        # survivors leaves the others erased and rebuilds none of them
        r = ec_impl.rows_per_chunk
        rec_rows = await aggregator.apply(D[[
            erasures.index(c) * r + j for c in need_rec for j in range(r)]],
            rows)
        erasures = tuple(need_rec)
    return ec_impl.decode_assemble(
        to_decode, want_chunks, erasures, need_rec, rec_rows)


async def _decode_chunks_async(
    sinfo, ec_impl, to_decode, want_chunks, *, service
) -> dict[int, np.ndarray] | None:
    """decode_payloads (matrix_base) with the matmul on the farm;
    None = caller should take the sync path."""
    if not to_decode:
        return None
    if not _farm_ready(service, ec_impl):
        return None
    if not isinstance(ec_impl, MatrixErasureCode) or ec_impl.get_sub_chunk_count() != 1:
        return None
    # same plan/rows/assemble pieces as the sync decode_payloads — the
    # algebra stays single-homed in matrix_base; only the matmul moves
    # onto the farm
    want_chunks = list(want_chunks)
    erasures, survivors, need_rec, D = ec_impl.decode_plan(to_decode, want_chunks)
    rec_rows = None
    if need_rec:
        rec_rows = await service.apply(
            D, ec_impl.decode_rows(to_decode, survivors))
    return ec_impl.decode_assemble(
        to_decode, want_chunks, erasures, need_rec, rec_rows)


def decode_concat(
    sinfo: StripeInfo,
    ec_impl: ErasureCodeInterface,
    to_decode: Mapping[int, np.ndarray],
) -> np.ndarray:
    """ECUtil::decode concat form (ECUtil.cc:12-48): shard payloads ->
    logical byte stream (all stripes' data chunks in order)."""
    assert to_decode
    cs, sw = sinfo.chunk_size, sinfo.stripe_width
    sizes = {len(np.asarray(v).reshape(-1)) for v in to_decode.values()}
    assert len(sizes) == 1, sizes
    total = sizes.pop()
    assert total % cs == 0
    if total == 0:
        return np.zeros(0, dtype=np.uint8)
    ns = total // cs
    k = ec_impl.get_data_chunk_count()

    if isinstance(ec_impl, MatrixErasureCode):
        chunks = ec_impl.decode_payloads(to_decode, range(k))
        # stripe s's logical bytes = concat of chunk 0..k-1 at stripe s
        stacked = np.stack([chunks[c].reshape(ns, cs) for c in range(k)], axis=1)
        return np.ascontiguousarray(stacked.reshape(ns * sw))

    outs = []
    for s in range(ns):
        sub = {
            shard: np.asarray(v)[s * cs : (s + 1) * cs]
            for shard, v in to_decode.items()
        }
        outs.append(ec_impl.decode_concat(sub))
    return np.concatenate(outs)


def decode_shards(
    sinfo: StripeInfo,
    ec_impl: ErasureCodeInterface,
    to_decode: Mapping[int, np.ndarray],
    need: set[int],
    *,
    packed_repair: bool = False,
) -> dict[int, np.ndarray]:
    """ECUtil::decode per-target-shard form (ECUtil.cc:50-121): rebuild
    full shard payloads for ``need`` (shard ids).  This is the recovery
    path.

    ``packed_repair`` declares the payload layout: True means each
    helper payload is the stripe-major concatenation of
    minimum_to_decode's sub-chunk runs (the regenerating-repair ranged
    read); False means full chunks.  The two layouts can be the same
    length (e.g. 2 stripes x half-chunk runs == 1 full chunk), so the
    caller must say which it read — guessing here silently corrupts
    the rebuilt shard."""
    assert to_decode
    cs = sinfo.chunk_size
    for v in to_decode.values():
        if len(np.asarray(v).reshape(-1)) == 0:
            return {s: np.zeros(0, dtype=np.uint8) for s in need}

    if (
        isinstance(ec_impl, MatrixErasureCode)
        and ec_impl.get_sub_chunk_count() == 1
    ):
        inv = {ec_impl.chunk_index(c): c for c in range(ec_impl.get_chunk_count())}
        chunks = ec_impl.decode_payloads(to_decode, [inv[s] for s in need])
        return {ec_impl.chunk_index(c): v for c, v in chunks.items()}

    first_len = len(np.asarray(next(iter(to_decode.values()))).reshape(-1))
    if packed_repair:
        avail = set(to_decode)
        minimum = ec_impl.minimum_to_decode(need, avail)
        sub_chunk = cs // ec_impl.get_sub_chunk_count()
        first_min = next(iter(minimum))
        per_chunk = sub_chunk * sum(c for _, c in minimum[first_min])
    else:
        per_chunk = cs
    chunks_count = first_len // per_chunk

    out: dict[int, list[np.ndarray]] = {s: [] for s in need}
    for i in range(chunks_count):
        piece = {
            shard: np.asarray(v)[i * per_chunk : (i + 1) * per_chunk]
            for shard, v in to_decode.items()
        }
        decoded = ec_impl.decode(need, piece, cs)
        for s in need:
            assert len(decoded[s]) == cs
            out[s].append(decoded[s])
    return {s: np.concatenate(bufs) for s, bufs in out.items()}


class HashInfo:
    """Cumulative per-shard crc32c chains stored as an object xattr
    (reference ECUtil.cc:164-248, hinfo_key).  Seeds start at -1 and
    each append chains the new chunk bytes onto the prior crc."""

    def __init__(self, num_chunks: int = 0):
        self.total_chunk_size = 0
        self.cumulative_shard_hashes: list[int] = [0xFFFFFFFF] * num_chunks
        self.projected_total_chunk_size = 0

    def has_chunk_hash(self) -> bool:
        return bool(self.cumulative_shard_hashes)

    def append(self, old_size: int, to_append: Mapping[int, np.ndarray]) -> None:
        assert old_size == self.total_chunk_size, (old_size, self.total_chunk_size)
        if not to_append:
            return
        size = len(next(iter(to_append.values())))
        if self.has_chunk_hash():
            assert len(to_append) == len(self.cumulative_shard_hashes)
            for shard, buf in to_append.items():
                assert len(buf) == size
                self.cumulative_shard_hashes[shard] = crc32c(
                    buf, self.cumulative_shard_hashes[shard]
                )
        self.total_chunk_size += size

    def clear(self) -> None:
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [0xFFFFFFFF] * len(
            self.cumulative_shard_hashes
        )

    def get_chunk_hash(self, shard: int) -> int:
        assert shard < len(self.cumulative_shard_hashes)
        return self.cumulative_shard_hashes[shard]

    def get_total_chunk_size(self) -> int:
        return self.total_chunk_size

    # projected size tracking for in-flight ops (ECUtil.h:105-140)
    def get_projected_total_chunk_size(self) -> int:
        return self.projected_total_chunk_size

    def set_projected_total_logical_size(self, sinfo: StripeInfo, size: int) -> None:
        self.projected_total_chunk_size = sinfo.logical_to_next_chunk_offset(size)

    def set_total_chunk_size_clear_hash(self, size: int) -> None:
        self.cumulative_shard_hashes = []
        self.total_chunk_size = size

    # -- xattr serialization (versioned, little-endian; our own denc) --
    def to_bytes(self) -> bytes:
        import struct

        n = len(self.cumulative_shard_hashes)
        return struct.pack(
            f"<BQI{n}I", 1, self.total_chunk_size, n, *self.cumulative_shard_hashes
        )

    @classmethod
    def from_bytes(cls, raw: bytes) -> "HashInfo":
        import struct

        ver, total, n = struct.unpack_from("<BQI", raw)
        assert ver == 1
        hi = cls(n)
        hi.total_chunk_size = total
        hi.cumulative_shard_hashes = list(
            struct.unpack_from(f"<{n}I", raw, struct.calcsize("<BQI"))
        )
        return hi
