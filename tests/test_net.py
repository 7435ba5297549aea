"""Real-socket transport tests: process clusters vs the in-process oracle.

Fast tier-1 coverage: address parsing, the chaos proxy as a transparent
pipe, and a 3-process UDS cluster whose digest must be bit-identical to the
``Cluster`` oracle on the same plan.  The heavyweight soaks (n=5, chaos on,
crash + AddServer join, TCP and UDS) run under ``--runslow``."""
import asyncio
import json
import os
import subprocess
import sys
import tempfile

import pytest

from repro.net.chaos import QUIET, ChaosConfig, ChaosProxy
from repro.net.harness import (Controller, make_plan, oracle_digest,
                               run_workload)
from repro.net.transport import parse_addr

REPO = os.path.join(os.path.dirname(__file__), "..")


def test_parse_addr():
    assert parse_addr("uds:/tmp/x.sock") == ("uds", "/tmp/x.sock")
    assert parse_addr("tcp:127.0.0.1:9000") == ("tcp", "127.0.0.1", 9000)
    with pytest.raises(ValueError):
        parse_addr("smtp:example.com:25")


def test_chaos_config_scaled_keeps_seed():
    cfg = ChaosConfig(seed=3).scaled(0.5)
    assert cfg.seed == 3
    assert cfg.drop_p == ChaosConfig().drop_p * 0.5
    assert all(getattr(QUIET, f) == 0.0
               for f in ("delay_p", "drop_p", "reorder_p", "bitflip_p",
                         "truncate_p"))


def test_quiet_proxy_is_a_transparent_pipe():
    async def run():
        with tempfile.TemporaryDirectory() as td:
            echoed = []

            msg = b"hello-chaos"

            async def echo(reader, writer):
                data = await reader.readexactly(len(msg))
                echoed.append(data)
                writer.write(data[::-1])
                await writer.drain()
                # Python >= 3.12: Server.wait_closed() waits for every
                # connection, so the handler closes its own
                writer.close()
                await writer.wait_closed()

            target, public = f"uds:{td}/real.sock", f"uds:{td}/pub.sock"
            from repro.net.transport import open_connection, start_server
            server = await start_server(target, echo)
            proxy = ChaosProxy(public, target, QUIET)
            await proxy.start()
            reader, writer = await open_connection(public)
            writer.write(msg)
            await writer.drain()
            reply = await reader.readexactly(len(msg))
            writer.close()
            await proxy.stop()
            server.close()
            await server.wait_closed()
            assert echoed == [msg]
            assert reply == msg[::-1]
            assert proxy.mutations == 0 and proxy.kills == 0
    asyncio.run(run())


def _run_cluster(n, *, transport, chaos, seed, phases=3, writes=2,
                 crash_phase=None, crash_sid=None,
                 add_phase=None, add_sid=None, add_seeds=(0, 1),
                 d=2, trace=True):
    """Spawn a process cluster, run the phased plan, return (result, plan,
    trace_dir)."""
    async def run(td):
        universe = list(range(n)) + ([add_sid] if add_sid is not None else [])
        ctl = Controller(td, universe, transport=transport, d=d,
                         chaos=chaos, hb_timeout=2.0,
                         trace_dir=td if trace else None)
        plan = make_plan(seed, n, phases=phases, writes_per_phase=writes,
                         submitters=[s for s in range(n) if s != crash_sid])
        try:
            res = await run_workload(ctl, plan, n,
                                     crash_phase=crash_phase,
                                     crash_sid=crash_sid,
                                     add_phase=add_phase, add_sid=add_sid,
                                     add_seeds=add_seeds)
        finally:
            await ctl.stop_all()
        return res, plan

    td_ctx = tempfile.TemporaryDirectory()
    with td_ctx as td:
        res, plan = asyncio.run(run(td))
        shard_data = {}
        for shard in res["shards"]:
            if os.path.exists(shard):
                shard_data[os.path.basename(shard)] = open(shard).read()
        return res, plan, shard_data


def test_three_process_uds_cluster_matches_oracle():
    seed = 11
    res, plan, _ = _run_cluster(3, transport="uds", chaos=None, seed=seed)
    digest, config = oracle_digest(plan, 3, d=2, seed=seed)
    assert res["digest"] == digest
    assert res["config"] == config == (0, 1, 2)
    assert res["decode_errors"] == 0   # no chaos: clean streams only


def test_three_process_cluster_survives_chaos():
    seed = 13
    cfg = ChaosConfig(seed=seed, delay_max=0.002)
    res, plan, _ = _run_cluster(3, transport="uds", chaos=cfg, seed=seed)
    digest, _ = oracle_digest(plan, 3, d=2, seed=seed)
    assert res["digest"] == digest, \
        "chaos may delay commands, never reorder or corrupt them"


def _merged_trace_checks(shard_data, tmpdir):
    """Write shards back out, merge them with trace_report --merge, and run
    the invariant gate on the merged trace."""
    shards = []
    for name, data in shard_data.items():
        p = os.path.join(tmpdir, name)
        with open(p, "w") as fh:
            fh.write(data)
        shards.append(p)
    merged = os.path.join(tmpdir, "merged.jsonl")
    script = os.path.join(REPO, "scripts", "trace_report.py")
    r = subprocess.run(
        [sys.executable, script, merged, "--merge", *shards, "--check"],
        capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": os.path.join(REPO, "src")})
    assert r.returncode == 0, f"trace_report --check failed:\n{r.stdout}\n{r.stderr}"
    ts = [json.loads(line)["t"] for line in open(merged)]
    assert ts == sorted(ts), "merged trace must be time-ordered"


@pytest.mark.slow
@pytest.mark.parametrize("transport", ["uds", "tcp"])
def test_soak_n5_chaos_crash_and_join(transport, tmp_path):
    """The PR's acceptance soak: a real 5-process cluster under byte-level
    chaos survives one crash and one AddServer join with zero invariant
    violations and a digest bit-identical to the Cluster oracle."""
    seed = 42
    crash_sid, add_sid = 4, 5
    cfg = ChaosConfig(seed=seed, delay_max=0.002)
    res, plan, shard_data = _run_cluster(
        5, transport=transport, chaos=cfg, seed=seed,
        phases=6, writes=3,
        crash_phase=1, crash_sid=crash_sid,
        add_phase=3, add_sid=add_sid, add_seeds=(0, 1))
    digest, config = oracle_digest(plan, 5, d=2, seed=seed,
                                   crash_phase=1, crash_sid=crash_sid,
                                   add_phase=3, add_sid=add_sid,
                                   add_seeds=(0, 1))
    assert res["digest"] == digest, "net digest diverged from the oracle"
    # the crash is a protocol fault, not an admin removal: the replicated
    # config still lists sid 4, and the join added sid 5
    assert res["config"] == config == (0, 1, 2, 3, 4, 5)
    assert any(st["eon"] >= 1 for st in res["statuses"]), \
        "the AddServer admin op must have flipped an eon"
    # the crashed worker exits via os._exit: its shard is never written,
    # and the merged-trace gate must hold regardless
    assert f"n{crash_sid}.jsonl" not in shard_data
    _merged_trace_checks(shard_data, str(tmp_path))
