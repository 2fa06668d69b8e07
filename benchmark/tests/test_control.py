"""The comparison that decides ``correct`` has been shown to fail: by
its control (the reference in the precision below, in the program's
place) and by a timed path broken underneath. XLA:CPU, toy sizes."""

import json

import pytest

import control
import run
import toy


@pytest.mark.parametrize("make", [toy.ring, toy.wave])
def test_control_fails_and_the_program_passes(make, tmp_path, capsys):
    """Three seeds: every sound run is correct, every control is not."""
    rc = control.main(["--workload", make(tmp_path), "--seconds", "0.2",
                       "--seeds", "5", "6", "3000000019"],
                      on_chip=False, extra_dir=str(tmp_path))
    out = capsys.readouterr().out.splitlines()
    lines = [json.loads(x) for x in out if x.startswith("{")]
    assert rc == 0, out
    assert len(lines) == 3
    for r in lines:
        assert r["correct"] and not r["control_correct"]
        assert not any(r["sound"].values())
        assert any(r["control"].values())


def _break_ring(monkeypatch):
    """A token altered where it is produced: one node's value comes
    out of every job one too high. Every gate still passes."""
    from timewarp_tpu.interp.jax_engine import fused_ring
    real = fused_ring.FusedRingEngine.run_quiet

    def broken(self, max_steps, state=None):
        st = real(self, max_steps, state)
        return st._replace(planes=st.planes.at[fused_ring._VAL, 0, 3].add(1))
    monkeypatch.setattr(fused_ring.FusedRingEngine, "run_quiet", broken)


def _break_wave(monkeypatch):
    """An answer altered where it is produced: every wave reports one
    delivery it did not make. Every gate still passes."""
    from timewarp_tpu.interp.jax_engine import engine
    real = engine.JaxEngine.run_quiet

    def broken(self, max_steps, state=None):
        st = real(self, max_steps, state)
        return st._replace(delivered=st.delivered + 1)
    monkeypatch.setattr(engine.JaxEngine, "run_quiet", broken)


def _break_wide_rung(monkeypatch):
    """A delay altered where it is produced, in the wide rungs of the
    routing ladder alone (the branches over 1024 senders): every
    seventh node gets its rumors one quantum late there. Every gate
    still passes, and so do the counts of nodes reached and messages
    delivered."""
    from timewarp_tpu.interp.jax_engine import engine
    real = engine.JaxEngine._sample_nodrop

    def broken(self, src, dst, tmsg, slot, woff, ok):
        flight, drel, *rest = real(self, src, dst, tmsg, slot, woff, ok)
        if src.shape[0] > 1024 * self.scenario.max_out:
            late = 1000 * (dst % 7 == 0)
            flight, drel = flight + late, drel + late.astype(drel.dtype)
        return (flight, drel, *rest)
    monkeypatch.setattr(engine.JaxEngine, "_sample_nodrop", broken)


def _wide_wave(base):
    return toy.wave(base, n_nodes=8192)


@pytest.mark.parametrize("make,breaker", [(toy.ring, _break_ring),
                                          (toy.wave, _break_wave),
                                          (_wide_wave, _break_wide_rung)])
def test_broken_timed_path_is_not_correct(make, breaker, tmp_path, capsys,
                                          monkeypatch):
    """A whole run, less the look for a chip, with the timed path
    broken underneath: ``correct`` comes out false."""
    breaker(monkeypatch)
    rc = run.run_cell(make(tmp_path), 7, 0.2, False, on_chip=False,
                      extra_dir=str(tmp_path))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out
    res = json.loads(out[-1])
    assert res["correct"] is False
    assert res["failed"] == 0       # the gates did not see it


def test_dropped_part_of_the_batch_fails_the_gates(tmp_path, capsys,
                                                   monkeypatch):
    """A step that returns its state unchanged: the warm-up job fails
    its gates and the run ends with no result line."""
    from timewarp_tpu.interp.jax_engine import fused_ring
    real = fused_ring.FusedRingEngine.run_quiet
    monkeypatch.setattr(fused_ring.FusedRingEngine, "run_quiet",
                        lambda self, max_steps, state=None:
                        real(self, 0, state))
    with pytest.raises(SystemExit, match="warm-up job failed"):
        run.run_cell(toy.ring(tmp_path), 7, 0.2, False, on_chip=False,
                     extra_dir=str(tmp_path))
    assert not capsys.readouterr().out.strip()
