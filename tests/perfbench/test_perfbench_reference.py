"""The host reference: its data, its verdicts, and its two controls."""
import copy

import jax.numpy as jnp
import numpy as np

from perfbench.reference import (HostReference, Keyspace, seed_words,
                                 short_key_answers, stale_answers, values_of)

SPACE = Keyspace(tenants=2, records=100, key_bits=24)
WORDS = seed_words(2**33 + 5)


def _val(key):
    return int(values_of(np.asarray([key], np.uint32), WORDS)[0])


def test_values_agree_between_numpy_and_jax():
    keys = np.arange(0, 1 << 22, 4099, dtype=np.uint32)
    assert (np.asarray(values_of(jnp.asarray(keys), WORDS, jnp))
            == values_of(keys, WORDS)).all()
    assert seed_words(1) != seed_words(2)


def _schedule():
    """Tick 0 reads key 5 of tenant 1 and updates it; tick 1 reads it back
    and deletes a key that is not there; tick 2 inserts and reads."""
    k = (1 << 24) | 5
    return [
        (0, "read", (k,), None, {"op": "read", "value": _val(k),
                                 "found": True}),
        (0, "update", (k,), 77, {"op": "update", "replaced": True,
                                 "ok": True}),
        (1, "read", (k,), None, {"op": "read", "value": 77, "found": True}),
        (1, "delete", (300,), None, {"op": "delete", "found": False}),
        (1, "rmw", (k,), 78, {"op": "rmw", "old": 77, "found": True,
                              "replaced": True, "ok": True}),
        (2, "scan", (k, k + 1), None, {"op": "scan", "values": [78, _val(k + 1)],
                                       "found": [True, True]}),
    ]


def test_a_sound_schedule_passes_and_counts_live_pairs():
    res = HostReference(SPACE, WORDS).check(_schedule(), window_end_tick=0)
    assert res == {"ops": 6, "wrong": 0, "first": [], "live_at_end": 200}


def test_a_flipped_answer_is_flagged():
    for i, field in ((0, "value"), (2, "value"), (3, "found"), (4, "old"),
                     (1, "replaced")):
        sched = copy.deepcopy(_schedule())
        res = sched[i][4]
        res[field] = res[field] ^ 1 if isinstance(res[field], int) \
            and not isinstance(res[field], bool) else not res[field]
        out = HostReference(SPACE, WORDS).check(sched)
        assert out["wrong"] >= 1, (i, field)
        assert out["first"][0][0] == sched[i][0]


def test_a_missing_answer_is_flagged():
    sched = copy.deepcopy(_schedule())
    del sched[1][4]["ok"]
    assert HostReference(SPACE, WORDS).check(sched)["wrong"] >= 1


def test_stale_reads_control_is_not_correct():
    answers = stale_answers(SPACE, WORDS, _schedule())
    assert HostReference(SPACE, WORDS).check(answers)["wrong"] >= 1


def test_short_key_control_is_not_correct():
    space = Keyspace(tenants=1, records=5000, key_bits=32)
    reads = [(t, "read", (k,), None, {}) for t, k in
             enumerate(range(4000, 5000))]
    sound = [(t, kind, keys, v, {"op": kind, "found": True,
                                 "value": _val(keys[0])})
             for t, kind, keys, v, _ in reads]
    assert HostReference(space, WORDS).check(sound)["wrong"] == 0
    # on 8 bits, keys 256 apart match; some share a bucket with an earlier one
    answers = short_key_answers(space, WORDS, reads, num_buckets=64,
                                salt=0x9E3779B9, bits=8)
    assert HostReference(space, WORDS).check(answers)["wrong"] >= 1
