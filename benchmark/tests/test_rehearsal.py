"""``run.py``'s job loop on XLA:CPU at toy sizes, through the test-only
entry that skips the TPU refusal (ring at 8192 nodes with the Pallas
interpreter, wave at 2048). Semantics and control flow only: nothing
printed here is a device number."""

import json

import numpy as np
import pytest

import run
import toy
from reference import gossip_ref, ring_ref

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def result_of(capsys, cell, seed, base, seconds=0.3, trace=False):
    rc = run.run_cell(cell, seed, seconds, trace, on_chip=False,
                      extra_dir=str(base))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    return json.loads(out[-1]), out


@pytest.mark.parametrize("make", [toy.ring, toy.wave])
def test_last_line_has_the_contracts_keys(make, tmp_path, capsys):
    """A cell ``run.py`` has never seen (a workload and a configuration
    file in a temporary directory) is found by name and run; the last
    line has the contract's keys and the end-to-end metrics."""
    res, out = result_of(capsys, make(tmp_path), 3_000_000_019, tmp_path)
    assert KEYS <= set(res)
    assert res["correct"] is True and res["failed"] == 0, out
    assert res["attempted"] >= 1
    assert {"msgs_per_s", "job_ms_p50", "setup_s"} <= set(res["metrics"])
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert any(line.startswith("compared ") for line in out)


def test_no_name_of_a_cell_config_or_metric_in_run_py():
    with open(run.__file__) as f:
        text = f.read()
    with open(run.os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    import re
    assert not [n for n in names
                if re.search(rf"(?<![\w.]){re.escape(n)}(?![\w.])", text)]


def test_ring_ref_equals_edge_engine_from_a_seeded_val(tmp_path):
    """The numpy recursion against ``EdgeEngine`` (XLA, no kernel) over
    12 supersteps from seeded token values; and the closed form for
    many supersteps against the recursion."""
    import jax.numpy as jnp
    from timewarp_tpu.interp.jax_engine.edge_engine import EdgeEngine
    from timewarp_tpu.models.token_ring import token_ring
    from timewarp_tpu.net.delays import FixedDelay
    n = 8192
    sc = token_ring(n, n_tokens=n, think_us=0, bootstrap_us=1000,
                    end_us=1 << 50, with_observer=False, mailbox_cap=4)
    eng = EdgeEngine(sc, FixedDelay(500), cap=2)
    val0 = np.random.default_rng(7).integers(0, n, n, dtype=np.int32)
    st = eng.init_state()
    st = st._replace(states={**st.states, "val": jnp.asarray(val0)})
    fin = eng.run_quiet(12, st)
    want = ring_ref.expect(val0, 12, bootstrap_us=1000, link_delay_us=500)
    assert np.array_equal(np.asarray(fin.states["val"]), want["val"])
    assert int(fin.delivered) == want["delivered"] == n * 11
    assert int(fin.time) == want["time"] and int(fin.steps) == 12
    assert int(fin.overflow) == 0
    for k in (1, 2, 3, 12, 100, 2 * n + 5):
        assert np.array_equal(ring_ref.val_after(val0[:512], k),
                              ring_ref.val_after_many(val0[:512], k))


def test_two_seeds_two_inputs_one_compile(tmp_path, capsys):
    """Two seeds give the ring two final states and the wave two
    origins, and the second seed compiles no driver again."""
    import importlib
    cell = toy.wave(tmp_path)
    traffic, config = run.load_cell(cell, str(tmp_path))
    builder = importlib.import_module("builders." + config["builder"])
    c = builder.Cell(config, traffic, interpret=True)
    c.set_up(11)
    a = [c.job(i) for i in (1, 2)]
    first = [k for k, _, _ in c.waves]
    c.set_up(3_000_000_019)             # warm-up job of the second seed
    assert c.engine.last_run_stats["compiles"] == 0
    c.job(1)
    assert c.waves[0][0] != first[0]
    assert not any(j["failed"] for j in a), a
    assert len({j["supersteps"] for j in a}
               | {c.job(1)["supersteps"]}) > 1

    cell = toy.ring(tmp_path)
    traffic, config = run.load_cell(cell, str(tmp_path))
    builder = importlib.import_module("builders." + config["builder"])
    r = builder.Cell(config, traffic, interpret=True)
    r.set_up(11)
    v1 = r.first_job["val"].copy()
    r.set_up(12)
    assert r.engine.last_run_stats["compiles"] == 0
    assert not np.array_equal(v1, r.first_job["val"])


def test_gossip_ref_runs_a_wave_like_the_engine(tmp_path):
    """The event-by-event reference against the final state of the
    engine's ``run_quiet`` at 8192 nodes, where the routing ladder has
    four rungs and a wave's crest (some 2100 pushes in one window)
    takes the wide ones: every node's hop count, the deliveries, the
    supersteps and the last one's time, from three origins."""
    import importlib
    traffic, config = run.load_cell(toy.wave(tmp_path, n_nodes=8192),
                                    str(tmp_path))
    builder = importlib.import_module("builders." + config["builder"])
    c = builder.Cell(config, traffic)
    assert len(c.engine._sender_rungs(c.n)) == 4
    c.set_up(5)
    for i in (1, 2, 3):
        assert not c.job(i)["failed"]
    g = gossip_ref.Graph(config["params"])
    assert len({k for k, _, _ in c.waves}) == 3
    for k, hop, facts in c.waves:
        want = g.wave(k)
        assert np.array_equal(np.asarray(hop), want.pop("hop"))
        assert facts == want
    assert [row[1] for row in c.compare(gossip_ref)] == [0] * 5


def test_no_mailbox_of_the_committed_wave_can_overflow():
    """The committed configuration's push graph: no node is the peer of
    more nodes than its mailbox has slots, so no wave from any origin
    under any latency draw can overflow one."""
    p = run.load_cell("gossip_100k.wave")[1]["params"]
    dst, distinct = gossip_ref.peers(p["n_nodes"], p["fanout"])
    in_degree = np.bincount(dst[distinct], minlength=p["n_nodes"])
    assert in_degree.max() == 21 <= p["mailbox_cap"]


def test_traced_run_reports_what_a_cpu_trace_holds(tmp_path, capsys):
    """``--trace 1`` on the CPU: the profiler's file holds no TPU
    plane, so the reduction refuses it by name (the per-layer metrics
    are device metrics and are read on the chip only)."""
    with pytest.raises(ValueError, match="XLA Ops"):
        run.run_cell(toy.ring(tmp_path), 1, 0.2, True, on_chip=False,
                     extra_dir=str(tmp_path))
    capsys.readouterr()


def test_refuses_without_a_tpu(tmp_path, capsys):
    rc = run.run_cell(toy.ring(tmp_path), 1, 0.2, False,
                      extra_dir=str(tmp_path))
    cap = capsys.readouterr()
    assert rc != 0 and "not a TPU" in cap.err
    assert not cap.out.strip()
