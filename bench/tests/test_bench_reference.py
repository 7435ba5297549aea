"""The plain references the benchmark decides ``correct`` with.

The round reference is cross-checked here against the program's own event
simulator and wire codec: two independent statements of one protocol and
one frame format, which the benchmark's runs never import.
"""
import json

import numpy as np
import pytest

from bench.harness import spec
from bench.harness.compare import rel_gap
from bench.reference import replay, rounds


def _config(name):
    with open(f"{spec.ROOT}/bench/configs/{name}.json") as f:
        return json.load(f)


def _event_timeline(algo, n, *, batch, network, d, k, payload=None):
    """Entry and completion ``[k, n]`` from ``repro.sim`` (the program)."""
    from repro.sim.runner import build_simulation
    sim, _met = build_simulation(algo, n, batch=batch, network=network, d=d)
    entries = [dict() for _ in range(n)]
    for h in range(n):
        real = sim.servers[h].payload_for

        def payload_for(rnd, _h=h, _real=real):
            entries[_h].setdefault(rnd, sim.now)
            return _real(rnd) if payload is None else payload
        sim.servers[h].payload_for = payload_for
    sim.start()
    sim.run(until=lambda: all(len(e) > k for e in entries), max_time=1e9)
    e = np.array([[entries[h][r] for h in range(n)]
                  for r in range(1, k + 2)])
    return e[:-1], e[1:]


@pytest.mark.parametrize("network", ["sdc", "mdc"])
@pytest.mark.parametrize("algo", ["allconcur+", "allconcur", "allgather"])
@pytest.mark.parametrize("n", [8, 16])
def test_rounds_equal_the_event_simulator(n, algo, network):
    c = _config("paper_fig34_sdc")
    d, offsets = c["degree"][str(n)], c["gr_offsets"][str(n)]
    for batch in (1, 133, 512):
        e, cmp_ = rounds.timeline(algo, n, network=network,
                                nbytes=rounds.sweep_bytes(batch), rounds=12,
                                offsets=offsets)
        e2, c2 = _event_timeline(algo, n, batch=batch, network=network, d=d,
                                 k=12)
        assert np.array_equal(e, e2) and np.array_equal(cmp_, c2)


def test_configured_offsets_are_the_programs_digraph():
    from repro.core.digraph import gs_digraph, resilience_degree
    c = _config("paper_fig34_sdc")
    for n in c["n"]:
        d = c["degree"][str(n)]
        assert d == resilience_degree(n)
        g = gs_digraph(list(range(n)), d)
        assert g.successors(0) == [o % n for o in c["gr_offsets"][str(n)]]


def test_frame_sizes_equal_the_codec():
    from repro.vecsim.topology import message_bytes, smr_message_bytes
    for b in (1, 2, 31, 32, 63, 64, 65, 100, 256, 512, 8191, 8192):
        for algo in ("allconcur+", "allconcur"):
            assert rounds.sweep_bytes(b) == message_bytes(algo, b)
        assert rounds.smr_bytes(min(b, 512), 16) == smr_message_bytes(
            "allconcur+", min(b, 512), value_size=16)


def test_configured_round_periods_are_the_references():
    c = _config("smr_acp_n8_b64")
    nb = rounds.smr_bytes(c["batch_max"], c["value_size"])
    e, _ = rounds.timeline("allconcur+", c["n"], network=c["network"],
                         nbytes=nb, rounds=16)
    assert float(e[-1, 0] - e[-2, 0]) == c["du"]
    e, cmp_ = rounds.timeline("allconcur", c["n"], network=c["network"],
                            nbytes=nb, rounds=16, offsets=c["gr_offsets"])
    assert float(cmp_[-1, 0] - e[-1, 0]) == c["dr"]


def test_smr_timeline_equals_the_event_simulator():
    c = _config("smr_acp_n8_b64")
    reqs = tuple((i % 64, 0, {"op": "put", "key": 0,
                              "value": ("v%d.0" % (i % 64)).ljust(16, "x")})
                 for i in range(c["batch_max"]))
    payload = {"kind": "smr", "src": 0, "round": 1,
               "batch": c["batch_max"], "reqs": reqs}
    nb = rounds.smr_bytes(c["batch_max"], c["value_size"])
    e, cmp_ = rounds.timeline("allconcur+", c["n"], network=c["network"],
                            nbytes=nb, rounds=40)
    e2, c2 = _event_timeline("allconcur+", c["n"], batch=c["batch_max"],
                             network=c["network"], d=3, k=40,
                             payload=payload)
    assert np.array_equal(e, e2) and np.array_equal(cmp_, c2)


def test_periodic_extension_matches_a_longer_run():
    c = _config("smr_acp_n8_b64")
    nb = rounds.smr_bytes(c["batch_max"], c["value_size"])
    kw = dict(network=c["network"], nbytes=nb)
    e, cmp_, period = rounds.periodic_timeline("allconcur+", c["n"],
                                               rounds=160, prefix=48, **kw)
    e2, c2 = rounds.timeline("allconcur+", c["n"], rounds=160, **kw)
    assert rel_gap(e, e2) < 1e-13 and rel_gap(cmp_, c2) < 1e-13
    assert rel_gap(period, np.full(c["n"], c["du"])) < 1e-12


def test_summary_counts_each_delivery_once():
    n, batch = 4, 2
    entry = np.arange(12, dtype=float)[:, None] + np.zeros((1, n))
    compl = entry + 1.0
    lat, thr = rounds.summary("allgather", n, batch, entry, compl, (3, 10))
    assert lat == 1.0 and thr == pytest.approx(7 * n * batch / 7.0)
    lat, _ = rounds.summary("allconcur+", n, batch, entry, compl, (3, 10))
    assert lat == 2.0


def test_failure_free_splice_is_rounds_times_du():
    du, dr = 2e-4, 7e-4
    never = np.full((3, 4), 1e12)
    entry, deliver, crashes = replay.splice(du, dr, never, rounds=50000,
                                            fd_timeout=0.01)
    k = np.arange(50000)
    assert rel_gap(entry[:, 1:], np.broadcast_to(k[1:] * du, (3, 49999))) \
        < 1e-15
    assert np.array_equal(deliver, entry + 2 * du)
    assert (crashes == 0).all()


def test_splice_crash_costs_detection_and_two_reliable_rounds():
    du, dr, fd = 1.0, 3.0, 10.0
    entry, deliver, crashes = replay.splice(
        du, dr, np.array([[2.5, 1e12]]), rounds=5, fd_timeout=fd)
    # rounds 0, 1 clean; round 2 starts at 2.0, crash at 2.5 wastes 0.5
    rec1 = 0.5 + fd + dr
    assert entry[0].tolist() == [0.0, 1.0, 2.0, 2.0 + rec1 + dr,
                                 3.0 + rec1 + dr]
    assert deliver[0, 2] == 2.0 + rec1 and crashes.tolist() == [1]


def test_round_index_capacity_recurrence():
    entry = np.array([[1.0, 2.0, 3.0, 4.0, 5.0]])
    submits = np.array([[0.5, 0.6, 0.7, 1.5, 9.0]])
    # batch 2, DUAL (delta 2): round 0 takes two, round 1 none (capacity
    # still held), round 2 the third and fourth; the last never arrives
    a0 = replay.round_index(entry, submits, delta=2, batch_max=2)
    assert a0.tolist() == [[0, 0, 2, 2, 5]]
    lat = replay.latencies(a0, entry + 0.25, submits, 1)
    assert np.allclose(lat, [1.75, 1.65, 3.55, 2.75])
    pct = replay.nearest_rank(lat, (0.5, 0.99))
    assert pct == {0.5: float(np.sort(lat)[2]), 0.99: float(np.max(lat))}
