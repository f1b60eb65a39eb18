"""Faults planted under the timed path, each of which must make a run not
``correct``: the controls of ``harness/verify.py``'s comparisons.  The
tests plant them in tiny rehearsals; ``control_on_chip.py`` plants them
in a cell at its own size on the chip.
"""

import contextlib

from harness import generator

KINDS = ("flip", "remove", "wrong_read")
WRONG_READ_AT = 40      # which read of the run is answered wrongly


def _flip(store, coll, obj) -> None:
    """One bit of the copy's first byte, through the store's own
    transaction, so that its checksum at rest agrees with the damage."""
    from ceph_tpu.store import Transaction

    first = bytes(store.read(coll, obj, 0, 1))
    store.queue_transaction(Transaction().write(
        coll, obj, 0, bytes([first[0] ^ 1])))


def _remove(store, coll, obj) -> None:
    from ceph_tpu.store import Transaction

    store.queue_transaction(Transaction().remove(coll, obj))


@contextlib.contextmanager
def _patched(owner, name: str, new):
    old = getattr(owner, name)
    setattr(owner, name, new)
    try:
        yield
    finally:
        setattr(owner, name, old)


def _damage_after_the_window(damage):
    """Once the loop has drained and before verify, hand ``damage`` the
    store, collection and object of the first live copy (the primary's)
    of one sampled object."""
    from ceph_tpu.store import coll_t, ghobject_t

    real = generator.Traffic.drain

    async def drain_then_damage(self):
        await real(self)
        c, name = self.c, sorted(self.sample())[0]
        pg, acting = c.acting_of(name)
        osd = next(o for o in acting if 0 <= o < c.n_osds
                   and c.osds[o] is not None)
        shard = acting.index(osd) if c.erasure else -1
        damage(c.osds[osd].store, coll_t(pg.pool, pg.ps, shard),
               ghobject_t(name, shard=shard))

    return _patched(generator.Traffic, "drain", drain_then_damage)


def _wrong_read():
    """The client's ``WRONG_READ_AT``-th read comes back with one bit of
    its last byte altered."""
    from ceph_tpu.client.rados import IoCtx

    real, calls = IoCtx.read, {"n": 0}

    async def read(self, oid, *a, **kw):
        got = await real(self, oid, *a, **kw)
        calls["n"] += 1
        if calls["n"] == WRONG_READ_AT:
            got = got[:-1] + bytes([got[-1] ^ 0x80])
        return got

    return _patched(IoCtx, "read", read)


def planted(kind: str):
    """A context in which the fault ``kind`` (one of ``KINDS``) is in
    place: ``flip`` a byte of one stored copy, ``remove`` one stored
    copy, answer one ``wrong_read``."""
    return {"flip": lambda: _damage_after_the_window(_flip),
            "remove": lambda: _damage_after_the_window(_remove),
            "wrong_read": _wrong_read}[kind]()
