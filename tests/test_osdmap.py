"""OSDMap pg->up/acting pipeline semantics.

Mirrors the invariants of the reference's TestOSDMap.cc: upmap tables,
EC positional holes, primary affinity, pg_temp overrides, stable-mod
folding (reference src/osd/OSDMap.cc:2670-2971).
"""

import pytest

from ceph_tpu.crush.builder import add_simple_rule, build_hierarchy
from ceph_tpu.crush.types import CRUSH_ITEM_NONE, CrushMap
from ceph_tpu.osd import OSDMap, PgPool, pg_t
from ceph_tpu.osd.types import PoolType, ceph_stable_mod


def make_osdmap(n_hosts=8, osds_per_host=4, ec=False, size=3, pg_num=64):
    cmap = CrushMap()
    cmap.type_names = {0: "osd", 1: "host", 10: "root"}
    root = build_hierarchy(cmap, osds_per_host, n_hosts)
    mode = "indep" if ec else "firstn"
    rule = add_simple_rule(cmap, root.id, 1, rule_type=3 if ec else 1, mode=mode)
    m = OSDMap(crush=cmap)
    n = n_hosts * osds_per_host
    for o in range(n):
        m.new_osd(o)
    m.pools[1] = PgPool(
        id=1,
        type=PoolType.ERASURE if ec else PoolType.REPLICATED,
        size=size,
        crush_rule=rule,
        pg_num=pg_num,
        pgp_num=pg_num,
    )
    return m


class TestBasicMapping:
    def test_replicated_full_size(self):
        m = make_osdmap()
        pool = m.pools[1]
        for ps in range(pool.pg_num):
            up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(1, ps))
            assert len(up) == 3
            assert len(set(up)) == 3
            assert upp == up[0]
            assert acting == up and actp == upp

    def test_distinct_failure_domains(self):
        m = make_osdmap()
        for ps in range(64):
            up, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
            hosts = {o // 4 for o in up}
            assert len(hosts) == len(up)

    def test_ec_full_size(self):
        m = make_osdmap(ec=True, size=5)
        for ps in range(64):
            up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(1, ps))
            assert len(up) == 5
            assert CRUSH_ITEM_NONE not in up

    def test_ec_one_spare_host_refills_any_lost_osd(self):
        """k+m+1 hosts, any one OSD out: every PG finds the one free
        host for the vacated slot.  That takes the EC rule's
        ``set_choose_tries 100`` (CrushWrapper::add_simple_rule); the
        tunables' 50 tries left 1-4 of these 128 PGs a hole for 10 of
        the 12 OSDs, and such a PG never recovers."""
        m = make_osdmap(n_hosts=12, osds_per_host=1, ec=True, size=11,
                        pg_num=128)
        for lost in range(12):
            weight = m.osd_weight[lost]
            m.mark_down(lost)
            m.mark_out(lost)
            for ps in range(128):
                up, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
                assert len(up) == 11 and lost not in up, (lost, ps, up)
                assert CRUSH_ITEM_NONE not in up, (lost, ps, up)
            m.mark_up(lost)
            m.osd_weight[lost] = weight

    def test_out_of_range_ps_folded_empty(self):
        m = make_osdmap(pg_num=64)
        assert m.pg_to_up_acting_osds(pg_t(1, 64), folded=True) == ([], -1, [], -1)

    def test_out_of_range_raw_ps_folds(self):
        # raw entry point folds ps via ceph_stable_mod (raw_pg_to_pg=true
        # branch, OSDMap.cc:2930)
        m = make_osdmap(pg_num=64)
        assert (
            m.pg_to_up_acting_osds(pg_t(1, 64))
            == m.pg_to_up_acting_osds(pg_t(1, 0))
        )

    def test_unknown_pool_empty(self):
        m = make_osdmap()
        assert m.pg_to_up_acting_osds(pg_t(7, 0)) == ([], -1, [], -1)

    def test_all_osds_used(self):
        m = make_osdmap(pg_num=256)
        used = set()
        for ps in range(256):
            up, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
            used.update(up)
        assert used == set(range(32))


class TestStableMod:
    def test_fold(self):
        # pg_num 12: mask 15; ps 13 & 15 = 13 >= 12 -> 13 & 7 = 5
        assert ceph_stable_mod(13, 12, 15) == 5
        assert ceph_stable_mod(3, 12, 15) == 3

    def test_non_pow2_pg_num_in_range(self):
        m = make_osdmap()
        m.pools[1].pg_num = m.pools[1].pgp_num = 12
        for ps in range(12):
            up, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
            assert len(up) == 3


class TestDownOsds:
    def test_replicated_shifts_left(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        m.mark_down(up0[0])
        up, upp, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        assert up == up0[1:]
        assert upp == up0[1]

    def test_ec_positional_hole(self):
        m = make_osdmap(ec=True, size=5)
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        m.mark_down(up0[2])
        up, upp, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        assert up[2] == CRUSH_ITEM_NONE
        assert up[:2] == up0[:2] and up[3:] == up0[3:]
        assert upp == up0[0]

    def test_dne_osd_ec_hole(self):
        m = make_osdmap(ec=True, size=5)
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        m.osd_state[up0[1]] = 0  # destroyed
        up, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        assert up[1] == CRUSH_ITEM_NONE

    def test_out_osd_remapped(self):
        # out (weight 0) but up: CRUSH rejects it, set stays full
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        m.mark_out(up0[0])
        up, *_ = m.pg_to_up_acting_osds(pg_t(1, 0))
        assert len(up) == 3
        assert up0[0] not in up


class TestUpmap:
    def test_explicit_pg_upmap(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 3))
        target = [o for o in range(32) if o not in up0][:3]
        m.pg_upmap[pg_t(1, 3)] = target
        up, *_ = m.pg_to_up_acting_osds(pg_t(1, 3))
        assert up == target

    def test_pg_upmap_rejected_when_target_out(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 3))
        target = [o for o in range(32) if o not in up0][:3]
        m.mark_out(target[1])
        m.pg_upmap[pg_t(1, 3)] = target
        up, *_ = m.pg_to_up_acting_osds(pg_t(1, 3))
        assert up == up0

    def test_pg_upmap_items_swap(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 5))
        new = next(o for o in range(32) if o not in up0)
        m.pg_upmap_items[pg_t(1, 5)] = [(up0[1], new)]
        up, *_ = m.pg_to_up_acting_osds(pg_t(1, 5))
        assert up == [up0[0], new, up0[2]]

    def test_pg_upmap_items_skipped_if_target_present(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 5))
        m.pg_upmap_items[pg_t(1, 5)] = [(up0[1], up0[2])]
        up, *_ = m.pg_to_up_acting_osds(pg_t(1, 5))
        assert up == up0

    def test_pg_upmap_items_skipped_if_target_out(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 5))
        new = next(o for o in range(32) if o not in up0)
        m.mark_out(new)
        m.pg_upmap_items[pg_t(1, 5)] = [(up0[1], new)]
        up, *_ = m.pg_to_up_acting_osds(pg_t(1, 5))
        assert up == up0

    def test_pg_upmap_primary_swap(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 9))
        m.pg_upmap_primaries[pg_t(1, 9)] = up0[2]
        up, upp, *_ = m.pg_to_up_acting_osds(pg_t(1, 9))
        assert upp == up0[2]
        assert up == [up0[2], up0[1], up0[0]]

    def test_pg_upmap_primary_not_in_set_ignored(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 9))
        new = next(o for o in range(32) if o not in up0)
        m.pg_upmap_primaries[pg_t(1, 9)] = new
        up, upp, *_ = m.pg_to_up_acting_osds(pg_t(1, 9))
        assert up == up0 and upp == up0[0]


class TestPrimaryAffinity:
    def test_zero_affinity_never_primary(self):
        m = make_osdmap()
        m.set_primary_affinity(3, 0)
        for ps in range(64):
            up, upp, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
            if 3 in up and len(up) > 1:
                assert upp != 3

    def test_affinity_moves_primary_to_front_replicated(self):
        m = make_osdmap()
        hits = 0
        for ps in range(64):
            up0, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
            m2 = make_osdmap()
            m2.set_primary_affinity(up0[0], 0)
            up, upp, *_ = m2.pg_to_up_acting_osds(pg_t(1, ps))
            if len(up) == 3 and up[0] != up0[0]:
                assert upp == up[0]
                assert up0[0] in up  # still a member, just not primary
                hits += 1
        assert hits > 0

    def test_ec_affinity_keeps_positions(self):
        m = make_osdmap(ec=True, size=5)
        up0, upp0, *_ = m.pg_to_up_acting_osds(pg_t(1, 2))
        m.set_primary_affinity(up0[0], 0)
        up, upp, *_ = m.pg_to_up_acting_osds(pg_t(1, 2))
        assert up == up0  # EC: no shifting, only primary designation
        assert upp != up0[0]

    def test_proportional_rejection(self):
        m = make_osdmap(pg_num=512)
        m.pools[1].pgp_num = 512
        # every osd at half affinity: distribution stays roughly uniform
        for o in range(32):
            m.set_primary_affinity(o, 0x8000)
        counts = {}
        for ps in range(512):
            _, upp, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
            counts[upp] = counts.get(upp, 0) + 1
        assert max(counts.values()) < 512 // 32 * 4


class TestPgTemp:
    def test_pg_temp_overrides_acting_not_up(self):
        m = make_osdmap()
        up0, upp0, *_ = m.pg_to_up_acting_osds(pg_t(1, 4))
        tmp = [o for o in range(32) if o not in up0][:3]
        m.pg_temp[pg_t(1, 4)] = tmp
        up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(1, 4))
        assert up == up0 and upp == upp0
        assert acting == tmp
        assert actp == tmp[0]

    def test_primary_temp(self):
        m = make_osdmap()
        up0, upp0, *_ = m.pg_to_up_acting_osds(pg_t(1, 4))
        m.primary_temp[pg_t(1, 4)] = up0[1]
        up, upp, acting, actp = m.pg_to_up_acting_osds(pg_t(1, 4))
        assert actp == up0[1]
        assert upp == upp0

    def test_pg_temp_down_members_filtered(self):
        m = make_osdmap()
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 4))
        tmp = [o for o in range(32) if o not in up0][:3]
        m.pg_temp[pg_t(1, 4)] = tmp
        m.mark_down(tmp[0])
        _, _, acting, actp = m.pg_to_up_acting_osds(pg_t(1, 4))
        assert acting == tmp[1:]
        assert actp == tmp[1]

    def test_pg_temp_ec_holes(self):
        m = make_osdmap(ec=True, size=3)
        up0, *_ = m.pg_to_up_acting_osds(pg_t(1, 4))
        tmp = [o for o in range(32) if o not in up0][:3]
        m.pg_temp[pg_t(1, 4)] = tmp
        m.mark_down(tmp[0])
        _, _, acting, actp = m.pg_to_up_acting_osds(pg_t(1, 4))
        assert acting == [CRUSH_ITEM_NONE] + tmp[1:]
        assert actp == tmp[1]


class TestChurn:
    def test_remap_stability(self):
        """Marking one OSD out moves only PGs that referenced it (plus
        the CRUSH rebalancing tail), never the whole cluster."""
        m = make_osdmap(pg_num=256)
        m.pools[1].pgp_num = 256
        before = {}
        for ps in range(256):
            before[ps], *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
        victim = 0
        m.mark_down(victim)
        m.mark_out(victim)
        moved = 0
        for ps in range(256):
            up, *_ = m.pg_to_up_acting_osds(pg_t(1, ps))
            if up != before[ps]:
                moved += 1
                assert victim in before[ps]
        assert moved > 0


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))


class TestMsrPools:
    def test_ec_pool_on_msr_rule_maps_positionally(self):
        """An EC pool whose profile sets crush-osds-per-failure-domain
        gets an MSR rule (reference ErasureCode::create_rule ->
        add_indep_multi_osd_per_failure_domain_rule) and the mapping
        pipeline serves it: full-size positional sets, <= osds-per-
        domain OSDs from any single failure domain."""
        from ceph_tpu.crush import builder as B
        from ceph_tpu.crush.types import CrushMap
        from ceph_tpu.osd.osdmap import OSDMap
        from ceph_tpu.osd.types import PgPool, PoolType, pg_t

        crush = CrushMap()
        B.build_hierarchy(crush, osds_per_host=4, n_hosts=4)
        om = OSDMap(crush=crush)
        for o in range(16):
            om.new_osd(o, weight=0x10000, up=True)
        rid = B.create_ec_rule(
            crush, "msr86", failure_domain="host",
            num_failure_domains=4, osds_per_failure_domain=3,
        )
        om.pools[1] = PgPool(
            id=1, type=PoolType.ERASURE, size=12, min_size=8,
            crush_rule=rid, pg_num=32, pgp_num=32,
        )
        host_of = {}
        for b in crush.buckets.values():
            if b.type == 1:
                for o in b.items:
                    if o >= 0:
                        host_of[o] = b.id
        for ps in range(32):
            up, _, acting, primary = om.pg_to_up_acting_osds(pg_t(1, ps))
            assert len(acting) == 12
            assert all(o >= 0 for o in acting), acting
            assert len(set(acting)) == 12
            per_host: dict = {}
            for o in acting:
                per_host[host_of[o]] = per_host.get(host_of[o], 0) + 1
            assert max(per_host.values()) <= 3, per_host
