"""A cell end to end on the CPU, at one university of three
departments, and the comparison that decides `correct` against the
control and the faults it must catch.

The runner is steered from here: the cell's configuration is cut to
that size and its rate and window shortened, and the harness is called
without the entry script's look for a chip.
"""
import copy
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.harness import Cell, run_cell
from repro.maintenance import MaintenanceReport

SEED = 2**31 + 7
E2E = {m["name"] for m in harness.load_benchmark()["end_to_end"]}


def _cell(name="lubm10.write-heavy", rate=40.0):
    cell = copy.deepcopy(Cell(name))
    cell.cfg["universities"] = 1
    cell.cfg["profile"]["departments_per_university"] = [3, 3]
    cell.rate = rate
    return cell


def _run(fault=None, maintenance=None, trace=False, seed=SEED):
    return run_cell(_cell(), seed, 3.0, trace=trace, fault=fault,
                    maintenance=maintenance)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_rehearsal_is_correct_and_reports_every_metric(sound):
    assert sound["correct"] is True
    assert sound["failed"] == 0
    assert set(sound["metrics"]) == E2E
    assert all(m["value"] > 0 for m in sound["metrics"].values())
    assert sound["attempted"] >= 60
    assert list(sound)[-1] == "checks"
    assert set(sound["device"]) >= {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    json.dumps(sound)


def test_traced_rehearsal_reports_host_layers():
    out = _run(trace=True)
    assert out["correct"] is True
    got = set(out["metrics"])
    # the CPU trace has no TPU plane: the device readers find nothing
    assert got == {"queue_wait_p95_ms", "serve_ms_per_read",
                   "maint_pass_ms", "compiles_in_window"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _unchanged_state(server):
    """Each maintenance pass returns with the store and views as they were."""
    def apply(delta):
        return MaintenanceReport(len(delta.inserts), len(delta.deletes), 0, 0)
    server.maintainer.apply = apply


def _altered_answer(server):
    """One row of every answer dropped where the answer is assembled."""
    ex = server.executor
    orig = ex.answer_group

    def answer_group(name):
        out = set(orig(name))
        if out:
            out.discard(min(out))
        return out
    ex.answer_group = answer_group


def _half_batch(server):
    """Every batch answers its first half and leaves the rest out."""
    orig = server.answer_batch

    def answer_batch(names):
        keep = (len(names) + 1) // 2
        return orig(names[:keep]) + [None] * (len(names) - keep)
    server.answer_batch = answer_batch


@pytest.mark.parametrize("fault,check", [
    (_unchanged_state, "wrong_answers"),
    (_altered_answer, "wrong_answers"),
    (_half_batch, "bad_tier_or_stale"),
])
def test_a_fault_in_the_timed_path_is_not_correct(fault, check):
    out = _run(fault=fault)
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


def test_control_breaking_the_freshness_guarantee_is_not_correct():
    # the program's own path with a staleness budget: reads are answered
    # without the updates submitted before them
    out = _run(maintenance={"staleness_budget": 10**6})
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_entry_script_needs_a_tpu():
    root = harness.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "lubm10.write-heavy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr
