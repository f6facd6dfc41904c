"""Profiler bulk surface: ``record_fast_many`` against a loop of
``record_fast``, its length check, and lazily-named entity blocks
(``reserve_entities``) interleaved with interned entities."""
import random

import numpy as np
import pytest

from repro.core.events import Profiler


def test_record_fast_many_matches_loop():
    rng = random.Random(3)
    times = [rng.uniform(0.0, 1e6) for _ in range(5000)]
    p_loop, p_bulk = Profiler(), Profiler()
    nid_l = p_loop.name_id("state:DONE")
    nid_b = p_bulk.name_id("state:DONE")
    assert nid_l == nid_b
    eids_l = [p_loop.entity_id(f"task.{i:06d}") for i in range(5000)]
    base = p_bulk.reserve_entities(5000, lambda i: f"task.{i:06d}")
    for t, e in zip(times, eids_l):
        p_loop.record_fast(t, e, nid_l)
    p_bulk.record_fast_many(np.asarray(times),
                            np.arange(base, base + 5000, dtype=np.int64),
                            nid_b)
    assert list(p_loop.time_column()) == list(p_bulk.time_column())
    assert list(p_loop.id_column()) == list(p_bulk.id_column())
    # lazy block naming resolves identically to interned entities
    for row in (0, 1234, 4999):
        assert (p_loop._event_at(row).entity
                == p_bulk._event_at(row).entity)
    assert p_loop.counts_by_name() == p_bulk.counts_by_name()


def test_record_fast_many_length_mismatch():
    p = Profiler()
    nid = p.name_id("x")
    with pytest.raises(ValueError):
        p.record_fast_many(np.zeros(3), np.zeros(2, dtype=np.int64), nid)


def test_reserve_entities_interleaves_with_interning():
    p = Profiler()
    a = p.entity_id("alpha")
    base = p.reserve_entities(10, lambda i: f"blk.{i}")
    b = p.entity_id("beta")
    assert b == base + 10 and a == 0
    assert p.entity_of(base + 3) == "blk.3"
    assert p.entity_of(b) == "beta"
    with pytest.raises(KeyError):
        p.entity_of(base + 10 + 99)
