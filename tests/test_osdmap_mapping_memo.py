"""The OSDMap's mapping memo, keyed by what the pipeline reads of a pg.

``OSDMap.pg_to_up_acting_osds`` memoizes per epoch under (pool, pg_num
fold, pgp_num fold): every object name of a PG shares one entry.  Here
the memoized answer for thousands of raw pgs is held to the pipeline's
own answer on a twin map whose memo is dropped before every call, on
replicated and EC pools whose pg_num and pgp_num differ (mid split), with
a pg_temp, a primary_temp, a pg_upmap_items entry and a primary affinity
below 1 in play; and every way the memo is invalidated makes the next
lookup run the pipeline again.
"""

import numpy as np
import pytest

from ceph_tpu.crush.builder import add_simple_rule, build_hierarchy
from ceph_tpu.crush.types import CrushMap
from ceph_tpu.osd import OSDMap, PgPool, pg_t
from ceph_tpu.osd.types import PoolType, ceph_stable_mod

N_HOSTS, OSDS_PER_HOST = 12, 2
N_RAW = 5000
POOLS = [   # (kind, pg_num, pgp_num)
    (kind, pg_num, pgp_num)
    for kind in ("replicated", "erasure")
    for pg_num, pgp_num in ((128, 128), (100, 64), (64, 32))]


def _make_map(kind: str, pg_num: int, pgp_num: int) -> OSDMap:
    """12 hosts of 2 OSDs, one pool (id 1): size 3 firstn, or an (8, 3)
    indep EC pool; a pg_temp, a primary_temp and a pg_upmap_items entry
    on three of its PGs, and two OSDs at primary affinity below 1."""
    ec = kind == "erasure"
    cmap = CrushMap()
    cmap.type_names = {0: "osd", 1: "host", 10: "root"}
    root = build_hierarchy(cmap, OSDS_PER_HOST, N_HOSTS)
    rule = add_simple_rule(cmap, root.id, 1, rule_type=3 if ec else 1,
                           mode="indep" if ec else "firstn")
    m = OSDMap(crush=cmap)
    for o in range(N_HOSTS * OSDS_PER_HOST):
        m.new_osd(o)
    m.pools[1] = PgPool(
        id=1, type=PoolType.ERASURE if ec else PoolType.REPLICATED,
        size=11 if ec else 3, crush_rule=rule,
        pg_num=pg_num, pgp_num=pgp_num)
    m.set_primary_affinity(3, 0x4000)
    m.set_primary_affinity(8, 0x8000)
    up, _p, _a, _ap = m.pg_to_up_acting_osds(pg_t(1, 1), folded=True)
    m.pg_temp[pg_t(1, 1)] = list(reversed(up))
    m.primary_temp[pg_t(1, 2)] = m.pg_to_up_acting_osds(
        pg_t(1, 2), folded=True)[0][-1]
    raw, _ = m.pg_to_raw_osds(pg_t(1, 5))
    spare = next(o for o in range(N_HOSTS * OSDS_PER_HOST) if o not in raw)
    m.pg_upmap_items[pg_t(1, 5)] = [(raw[1], spare)]
    m.epoch = 7
    return m


def _counts(m: OSDMap) -> tuple[float, float]:
    d = m.mapping_perf.dump()
    return d.get("pg_mapping_lookups", 0), d.get("pg_mapping_runs", 0)


def _folds(pool: PgPool, ps: int) -> tuple[int, int]:
    return (ceph_stable_mod(ps, pool.pg_num, pool.pg_num_mask),
            ceph_stable_mod(ps, pool.pgp_num, pool.pgp_num_mask))


@pytest.mark.parametrize("kind, pg_num, pgp_num", POOLS)
def test_the_memo_answers_as_the_pipeline_does(kind, pg_num, pgp_num):
    memo = _make_map(kind, pg_num, pgp_num)
    ref = _make_map(kind, pg_num, pgp_num)
    pool = memo.pools[1]
    raws = np.random.default_rng(43 + pg_num + pgp_num).integers(
        0, 1 << 32, N_RAW, dtype=np.uint64).tolist()
    lookups0, runs0 = _counts(memo)
    for ps in raws:
        ref.invalidate_mapping_cache()
        want = ref.pg_to_up_acting_osds(pg_t(1, ps))
        got = memo.pg_to_up_acting_osds(pg_t(1, ps))
        assert got == want, (ps, _folds(pool, ps))
        got[0].append(-7)   # the answers are copies: the memo keeps its own
        got[2].clear()
    pairs = {_folds(pool, ps) for ps in raws}
    lookups, runs = _counts(memo)
    assert lookups - lookups0 == N_RAW
    assert runs - runs0 <= len(pairs) < N_RAW
    # every PG seen: a folded lookup shares the entry of its raw pgs
    assert {p for p, _ in pairs} == set(range(pg_num))
    for ps in range(pg_num):
        ref.invalidate_mapping_cache()
        assert memo.pg_to_up_acting_osds(pg_t(1, ps), folded=True) == \
            ref.pg_to_up_acting_osds(pg_t(1, ps), folded=True)
    assert _counts(memo)[1] == runs
    # the exception tables are in play: each moves its PG off plain CRUSH
    assert memo.pg_to_up_acting_osds(pg_t(1, 1), folded=True)[2] == list(
        reversed(memo.pg_to_up_acting_osds(pg_t(1, 1), folded=True)[0]))
    assert memo.pg_to_up_acting_osds(pg_t(1, 2), folded=True)[3] == \
        memo.primary_temp[pg_t(1, 2)]
    (frm, to), = memo.pg_upmap_items[pg_t(1, 5)]
    up5 = memo.pg_to_up_acting_osds(pg_t(1, 5), folded=True)[0]
    assert to in up5 and frm not in up5


def _bump_epoch(m: OSDMap) -> None:
    m.epoch += 1


INVALIDATIONS = {
    "epoch": _bump_epoch,
    "invalidate_mapping_cache": OSDMap.invalidate_mapping_cache,
    "mark_down": lambda m: m.mark_down(20),
    "mark_up": lambda m: m.mark_up(20),
    "mark_out": lambda m: m.mark_out(20),
    "new_osd": lambda m: m.new_osd(24),
    "set_primary_affinity": lambda m: m.set_primary_affinity(4, 0x2000),
    "osd_weight": lambda m: m.osd_weight.__setitem__(21, 0x8000),
    "osd_state": lambda m: m.osd_state.__setitem__(21, 1),
    "pg_temp": lambda m: m.pg_temp.__setitem__(pg_t(1, 9), [0, 2, 4]),
    "primary_temp": lambda m: m.primary_temp.pop(pg_t(1, 2)),
    "pg_upmap": lambda m: m.pg_upmap.__setitem__(pg_t(1, 9), [0, 2, 4]),
    "pg_upmap_items": lambda m: m.pg_upmap_items.clear(),
    "pg_upmap_primaries": lambda m: m.pg_upmap_primaries.update(
        {pg_t(1, 9): 2}),
}


@pytest.mark.parametrize("how", sorted(INVALIDATIONS))
def test_each_invalidation_makes_the_next_lookup_run(how):
    m = _make_map("replicated", 100, 64)
    raw = pg_t(1, 0x9E3779B9)
    m.pg_to_up_acting_osds(raw)
    runs = _counts(m)[1]
    m.pg_to_up_acting_osds(raw)
    assert _counts(m)[1] == runs
    INVALIDATIONS[how](m)
    m.pg_to_up_acting_osds(raw)
    assert _counts(m)[1] == runs + 1
    m.pg_to_up_acting_osds(raw)
    assert _counts(m)[1] == runs + 1


@pytest.mark.parametrize("kind", ["replicated", "erasure"])
def test_a_folded_pg_out_of_range_is_empty_and_looks_nothing_up(kind):
    m = _make_map(kind, 100, 64)
    before = _counts(m)
    for ps in (100, 127, 128, 1 << 20):
        assert m.pg_to_up_acting_osds(pg_t(1, ps), folded=True) == (
            [], -1, [], -1)
    assert m.pg_to_up_acting_osds(pg_t(9, 0)) == ([], -1, [], -1)
    assert _counts(m) == before
    # the same ps as a raw pg folds into range and maps
    assert len(m.pg_to_up_acting_osds(pg_t(1, 100))[0]) == m.pools[1].size
