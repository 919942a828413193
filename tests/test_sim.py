"""ISSUE-17 acceptance tests for the trace-driven fleet simulator.

The contracts the sim subsystem pins:

- virtual time is monotone — the clock and event loop refuse to move
  backwards, and every replay's event log is time-ordered;
- determinism — same trace + same seed + same config produce a
  byte-identical event log (different seeds diverge);
- speed — replaying the committed fixture takes a small fraction, in
  CPU time and on the wall clock, of the span it recorded;
- fidelity — replaying the fixture under the live fleet's config
  reproduces the live per-phase and end-to-end p50/p99 within 15%
  (0.25 ms floor) over the steady-state window;
- the tuner beats the default config on SLO burn, deterministically;
- the autoscaler and rollout controller run correctly on virtual time;
- ``ci/perf_gate.py --sim`` passes against the committed artifact.
"""

import json
import time
from pathlib import Path

import pytest

from sparkdl_tpu.sim import (
    DEFAULT_CONFIG,
    EventLoop,
    FleetReplay,
    TraceRecord,
    VirtualClock,
    fidelity_report,
    load_trace,
    summarize,
    write_trace,
)
from sparkdl_tpu.sim.clock import ClockWentBackwards
from sparkdl_tpu.sim.tune import DEFAULT_SPACE, EVAL_HARNESS, tune

_REPO = Path(__file__).resolve().parent.parent
FIXTURE = _REPO / "tests" / "fixtures" / "sim_trace_small.jsonl"

#: the demo fleet config the fixture was recorded against
#: (serving/replica.py factory defaults) — fidelity replays must match
#: the live run's knobs, not the sim's defaults
LIVE_CONFIG = {
    "replicas": 2, "max_batch": 16, "max_wait_ms": 1.0,
    "queue_capacity": 512,
}

#: the one-time warmup-compile era: its placement cascade is not
#: recoverable from the trace, so fidelity is judged on steady state
WARMUP_S = 1.0


@pytest.fixture(scope="module")
def fixture_trace():
    meta, records = load_trace(str(FIXTURE))
    assert meta.get("kind") == "sparkdl_trace"
    assert records
    return meta, records


# ---------------------------------------------------------------------------
# virtual clock discipline
# ---------------------------------------------------------------------------

def test_virtual_clock_never_goes_backwards():
    clock = VirtualClock()
    clock.advance_to(1.5)
    clock.advance_to(1.5)  # idempotent re-advance is fine
    assert clock.now == 1.5
    with pytest.raises(ClockWentBackwards):
        clock.advance_to(1.0)


def test_event_loop_rejects_scheduling_in_the_past():
    clock = VirtualClock()
    loop = EventLoop(clock)
    clock.advance_to(2.0)
    with pytest.raises(ClockWentBackwards):
        loop.schedule(1.0, lambda: None)


def test_event_loop_runs_in_time_order():
    clock = VirtualClock()
    loop = EventLoop(clock)
    seen = []
    for t in (3.0, 1.0, 2.0):
        loop.schedule(t, seen.append, t)
    loop.run()
    assert seen == [1.0, 2.0, 3.0]
    assert clock.now == 3.0


def test_replay_event_log_is_time_monotone(fixture_trace):
    _, records = fixture_trace
    fr = FleetReplay(records, config=LIVE_CONFIG, seed=0)
    fr.run()
    times = [row["t"] for row in fr.event_log]
    assert times, "replay produced no events"
    assert all(a <= b for a, b in zip(times, times[1:]))


# ---------------------------------------------------------------------------
# trace format
# ---------------------------------------------------------------------------

def test_trace_write_load_roundtrip(tmp_path):
    path = tmp_path / "t.jsonl"
    records = [
        TraceRecord(t=0.1, endpoint="ep0", tenant="a", outcome="ok",
                    latency_ms=3.2, server_ms=1.1,
                    phases={"forward": 1.0, "wire": 0.2}),
        TraceRecord(t=0.2, endpoint="ep1", outcome="shed"),
    ]
    n = write_trace(str(path), {"benchmark": "x"}, records)
    assert n == 2
    meta, loaded = load_trace(str(path))
    assert meta["kind"] == "sparkdl_trace" and meta["benchmark"] == "x"
    assert [r.to_json() for r in loaded] == [r.to_json() for r in records]


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

def test_same_seed_same_trace_byte_identical_event_log(fixture_trace):
    _, records = fixture_trace
    runs = [FleetReplay(records, config=LIVE_CONFIG, seed=7)
            for _ in range(2)]
    reports = [fr.run() for fr in runs]
    assert runs[0].event_log_bytes() == runs[1].event_log_bytes()
    assert (reports[0]["event_log_sha256"]
            == reports[1]["event_log_sha256"])


def test_different_seed_diverges(fixture_trace):
    _, records = fixture_trace
    a = FleetReplay(records, config=LIVE_CONFIG, seed=0).run()
    b = FleetReplay(records, config=LIVE_CONFIG, seed=1).run()
    assert a["event_log_sha256"] != b["event_log_sha256"]


def test_replay_runs_once(fixture_trace):
    _, records = fixture_trace
    fr = FleetReplay(records[:16], config=LIVE_CONFIG, seed=0)
    fr.run()
    with pytest.raises(RuntimeError):
        fr.run()


def test_unknown_config_key_rejected(fixture_trace):
    _, records = fixture_trace
    with pytest.raises(KeyError):
        FleetReplay(records[:4], config={"max_bacth": 8})


# ---------------------------------------------------------------------------
# speed + fidelity (the ISSUE-17 acceptance numbers)
# ---------------------------------------------------------------------------

def test_replay_is_far_faster_than_the_time_it_replays(fixture_trace):
    _, records = fixture_trace
    # Two ratios of the replayed (virtual) seconds, best of three (the
    # first run pays import/alloc warmup).  Over the replay's own CPU
    # time (``process_time``, which a descheduled worker does not run up):
    # what the event loop costs.  Over the wall clock: that nothing in it
    # waits on real time.  The recorded claim of 100x was another host's;
    # this sandbox's CPU gives 65-72x of either alone, so both are held
    # at margins a loaded six-worker run on it cannot miss, and a replay
    # that sleeps (1x) or a quadratic event loop still does.
    by_cpu, by_wall = [], []
    for _ in range(3):
        wall0, cpu0 = time.perf_counter(), time.process_time()
        rep = FleetReplay(records, config=LIVE_CONFIG, seed=0).run()
        by_cpu.append(rep["virtual_s"] / (time.process_time() - cpu0))
        by_wall.append(rep["virtual_s"] / (time.perf_counter() - wall0))
    assert max(by_cpu) >= 25.0, f"over CPU time: {by_cpu}"
    assert max(by_wall) >= 10.0, f"over the wall clock: {by_wall}"


def test_steady_state_fidelity_within_15_percent(fixture_trace):
    _, records = fixture_trace
    fr = FleetReplay(records, config=LIVE_CONFIG, seed=0)
    fr.run()
    live_steady = summarize(
        [r for r in records if r.t >= WARMUP_S]
    )
    sim_steady = summarize(
        [r for r in fr.results if r.t >= WARMUP_S]
    )
    fid = fidelity_report(live_steady, sim_steady,
                          tolerance=0.15, floor_ms=0.25)
    failing = {k: v for k, v in fid["rows"].items() if not v["ok"]}
    assert fid["pass"], f"fidelity misses: {json.dumps(failing)}"
    # the comparison actually covered the signal, not a vacuous pass
    assert "e2e.p99" in fid["rows"]
    assert any(k.startswith("phase.") for k in fid["rows"])


def test_replay_report_shape(fixture_trace):
    _, records = fixture_trace
    rep = FleetReplay(records, config=LIVE_CONFIG, seed=0).run()
    assert rep["benchmark"] == "sim_replay" and rep["sim"] is True
    assert rep["requests"] == len(records)
    assert rep["ok"] + rep["shed"] + rep["expired"] <= rep["requests"]
    assert rep["latency_ms"]["p99"] is not None
    assert rep["slo"]["p99_threshold_ms"] > 0


# ---------------------------------------------------------------------------
# tuner
# ---------------------------------------------------------------------------

def test_tune_beats_default_on_burn_deterministically(fixture_trace):
    _, records = fixture_trace
    artifacts = [
        tune(records, space=DEFAULT_SPACE, budget=8, seed=3,
             time_scale=4.0)
        for _ in range(2)
    ]
    texts = [json.dumps(a, sort_keys=True) for a in artifacts]
    assert texts[0] == texts[1], "tune() is not deterministic"
    art = artifacts[0]
    rec, dfl = art["recommended"], art["default"]
    assert rec["burn_integral"] <= dfl["burn_integral"]
    assert rec["score"] <= dfl["score"]
    assert art["improvement"]["score"] >= 0
    # the stress dial did its job: the default config actually burns,
    # so the win is over a non-trivial baseline
    assert dfl["burn_integral"] > 0


def test_knob_space_rejects_typo():
    from sparkdl_tpu.sim.tune import Knob, KnobSpace
    with pytest.raises(KeyError):
        KnobSpace([Knob("max_bacth", "choice", choices=(8,))])


# ---------------------------------------------------------------------------
# controllers on virtual time
# ---------------------------------------------------------------------------

def test_autoscaler_scales_up_under_stress(fixture_trace):
    _, records = fixture_trace
    cfg = {
        "replicas": 1,
        "autoscale": {
            "min": 1, "max": 4, "interval_s": 0.5, "cooldown_s": 0.5,
            "step_up": 2, "ok_streak": 2, "per_replica_inflight": 8,
        },
        "tick_s": 0.25, "slo_fast_s": 1.0, "slo_slow_s": 2.5,
    }
    rep = FleetReplay(records, config=cfg, seed=0, time_scale=4.0).run()
    decisions = rep["autoscale"]["decisions"]
    assert decisions, "autoscaler never ticked"
    assert rep["autoscale"]["target"] > 1, decisions
    # targets respect the declared bounds at every decision
    assert all(
        1 <= d["replicas_after"] <= 4 for d in decisions
    ), decisions


def test_rollout_promotes_clean_canary(fixture_trace):
    _, records = fixture_trace
    cfg = {
        "rollout": {
            "new_version": "v2", "replicas": 2, "stages": (0.5, 1.0),
            "bake_s": 0.5, "interval_s": 0.25, "regress_ms": 0.0,
            # above the warmup-compile tail: a clean canary must not
            # page on the one-time first-touch compiles
            "slo_p99_ms": 300.0,
        },
        "tick_s": 0.25,
    }
    fr = FleetReplay(records, config=cfg, seed=0)
    rep = fr.run()
    assert rep["rollout"]["state"] == "done", rep["rollout"]
    assert fr.supervisor.primary_version == "v2"


def test_rollout_rolls_back_regressed_canary(fixture_trace):
    _, records = fixture_trace
    cfg = {
        "rollout": {
            "new_version": "v2", "replicas": 2, "stages": (0.5, 1.0),
            "bake_s": 0.5, "interval_s": 0.25,
            # the new version is 500 ms slower: the canary SLO pages
            "regress_ms": 500.0, "slo_p99_ms": 300.0,
        },
        "tick_s": 0.25,
    }
    fr = FleetReplay(records, config=cfg, seed=0)
    rep = fr.run()
    assert rep["rollout"]["state"] == "rolled_back", rep["rollout"]
    assert fr.supervisor.primary_version != "v2"


# ---------------------------------------------------------------------------
# CI integration
# ---------------------------------------------------------------------------

def test_perf_gate_sim_flavor_passes_on_committed_artifact():
    from ci.perf_gate import DEFAULT_SIM_ARTIFACT, DEFAULT_SIM_TRACE, gate_sim
    verdict = gate_sim(str(_REPO / DEFAULT_SIM_TRACE),
                       str(_REPO / DEFAULT_SIM_ARTIFACT))
    failing = [r for r in verdict["rows"] if not r["ok"]]
    assert verdict["ok"], failing
    metrics = {r["metric"] for r in verdict["rows"]}
    assert metrics == {
        "sim.deterministic",
        "sim.recommended_burn_vs_default",
        "sim.recommended_burn_drift",
    }


def test_shape_key_separates_sim_from_live_reports():
    from ci.perf_gate import shape_key
    base = {
        "benchmark": "bench_load", "scenario": "steady",
        "duration_s": 8, "rate": 150, "latency_ms": {"p50": 1.0},
    }
    live = shape_key(base)
    sim = shape_key({**base, "sim": True})
    assert live != sim


def test_eval_harness_keys_are_replay_config_keys():
    # the tuner merges EVAL_HARNESS over every candidate; a drifted key
    # would make _merge_config reject every trial
    assert set(EVAL_HARNESS) <= set(DEFAULT_CONFIG)
