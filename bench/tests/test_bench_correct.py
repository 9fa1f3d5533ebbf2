"""The check that decides ``correct``, driven end to end on the CPU at
a small size: a sound run passes, the control (the reference in three
bf16 passes in the program's place) fails the same limits, and a run
whose timed path is broken underneath reads ``correct`` false for each
fault a serving cell can have, state left un-updated among them.  (A
one-chip cell has no exchange between chips to leave out.)"""

import time

import numpy as np
import pytest

from bench import correctness, harness

SECONDS = 2.0


def _run(spec, bench, seed=2**31 + 3):
    import jax
    result, compared = harness.run(spec, bench, seed=seed, seconds=SECONDS,
                                   trace=False, devices=jax.devices()[:1],
                                   t_start=time.perf_counter())
    return result, compared


def test_sound_run_is_correct(tiny_spec, bench_spec):
    result, compared = _run(tiny_spec, bench_spec)
    assert result["correct"], compared
    # sessions finished and were replaced, so prefill ran in the window
    assert set(compared) == {"decode_rel_err", "prefill_rel_err"}
    assert list(result)[-1] == "compared"
    assert {"tpot_p95_ms", "output_tokens_per_s",
            "setup_s"} == set(result["metrics"])
    assert result["attempted"] > 4 and result["failed"] == 0


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_control_fails_the_limits(tiny_spec, seed):
    sched, _, window, rec = harness.prepare(tiny_spec, seed, False,
                                            time.perf_counter())
    rec.armed = True
    window.run(SECONDS)
    rec.armed = False
    calls, served = harness.sampled_calls(rec)
    prog, ctrl = correctness.readings(tiny_spec["config"], calls, served,
                                      weight_seed=seed, kv_extent=32,
                                      control=True)
    limits = tiny_spec["config"]["correct_limits"]
    assert set(prog) == set(ctrl) == set(limits)
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert any(ctrl[k] > limits[k] for k in limits), ctrl
    assert any(ctrl[k] > 3 * prog[k] for k in limits), (prog, ctrl)


def _stale_state(orig):
    """A decode tick that returns each request's state unchanged: the
    previous tick's outputs, not this one's."""
    last = {}

    def run_batch(self, backend, envs, **kw):
        finals = orig(self, backend, envs, **kw)
        out = [last.get(i, f) for i, f in enumerate(finals)]
        last.update(enumerate(finals))
        return out
    return run_batch


def _half_batch(orig):
    """Half of the batch left out, the mean of the rest in its place."""
    def run_batch(self, backend, envs, **kw):
        keep = max(1, len(envs) // 2)
        finals = orig(self, backend, envs[:keep], **kw)
        mean = np.mean(np.stack(finals), axis=0)
        return finals + [mean] * (len(envs) - keep)
    return run_batch


def _altered_answer(orig):
    """One value of every GEMM's output altered where it is produced."""
    def run_program(self, program, tensors=None):
        out = orig(self, program, tensors)
        res = np.array(out[program.out_name])
        res.flat[0] += 1.0
        out[program.out_name] = res
        return out
    return run_program


def _kept_carry(orig):
    """A tick whose output is served but not carried: the next tick's
    input still comes from the request's previous output."""
    def after_decode(self, a, final):
        carry = a.carry
        orig(self, a, final)
        a.carry = carry
    return after_decode


def _skipped_commit(orig):
    """Outputs never folded into the request's K/V."""
    def commit(self, out, pos):
        return None
    return commit


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_answer", "kept_carry",
                                   "skipped_commit"])
def test_broken_timed_path_is_not_correct(tiny_spec, bench_spec,
                                          monkeypatch, fault):
    from repro.backends.pallas_backend import PallasBackend
    from repro.runtime.executable import ModelExecutable
    from repro.runtime.scheduler import PagedKV, Scheduler
    if fault == "altered_answer":
        monkeypatch.setattr(PallasBackend, "run_program",
                            _altered_answer(PallasBackend.run_program))
    elif fault == "kept_carry":
        monkeypatch.setattr(Scheduler, "_after_decode",
                            _kept_carry(Scheduler._after_decode))
    elif fault == "skipped_commit":
        monkeypatch.setattr(PagedKV, "commit",
                            _skipped_commit(PagedKV.commit))
    else:
        make = _stale_state if fault == "stale_state" else _half_batch
        monkeypatch.setattr(ModelExecutable, "run_batch",
                            make(ModelExecutable.run_batch))
    result, compared = _run(tiny_spec, bench_spec)
    assert result["correct"] is False, compared
