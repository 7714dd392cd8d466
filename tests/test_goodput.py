"""Goodput ledger tests (docs/goodput.md).

The acceptance contract of the attribution layer: phases are exclusive
and conserve wall-clock (they sum to elapsed, ``unattributed`` being
the exact remainder), the honesty bucket stays bounded and nameable,
the fleet merge names the dominant bottleneck with per-rank evidence,
and the SLO burn alert fires.
"""

import json
import os
import time

import pytest

from horovod_tpu.perf import goodput as gp
from test_multiprocess import run_ranks


@pytest.fixture(autouse=True)
def _fresh_ledger():
    gp.reset()
    yield
    gp.reset()


def _fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    clock.advance = lambda dt: t.__setitem__(0, t[0] + dt)
    return clock


# ---------------------------------------------------------------------------
# Ledger state machine
# ---------------------------------------------------------------------------


def test_phases_are_exclusive_and_conserve_wall_clock():
    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    clock.advance(2.0)
    led.observe("init", 2.0)
    clock.advance(5.0)
    led.observe("compile", 4.0)          # 1 s gap -> unattributed
    clock.advance(3.0)
    led.observe_step(3.0, compute=2.0, comm_exposed=0.7, input_wait=0.3)
    snap = led.snapshot()
    assert snap["elapsed_s"] == pytest.approx(10.0)
    assert snap["phases"]["init"] == pytest.approx(2.0)
    assert snap["phases"]["compile"] == pytest.approx(4.0)
    assert snap["phases"]["compute"] == pytest.approx(2.0)
    assert snap["phases"]["comm_exposed"] == pytest.approx(0.7)
    assert snap["phases"]["input_wait"] == pytest.approx(0.3)
    assert snap["unattributed_s"] == pytest.approx(1.0)
    total = sum(snap["phases"].values()) + snap["unattributed_s"]
    assert total == pytest.approx(snap["elapsed_s"], rel=1e-9)
    assert snap["goodput_ratio"] == pytest.approx(0.2)
    assert snap["unattributed_ratio"] == pytest.approx(0.1)


def test_unstarted_ledger_is_empty_and_unknown_phase_rejected():
    led = gp.GoodputLedger(clock=_fake_clock())
    assert led.snapshot()["elapsed_s"] == 0.0
    with pytest.raises(ValueError):
        led.observe("naptime", 1.0)
    # "unattributed" is synthesized, never directly observable
    with pytest.raises(ValueError):
        led.observe("unattributed", 1.0)


def test_observe_step_budget_clamps_oversized_parts():
    """A step's parts can never exceed its wall: priority order
    input_wait -> comm_exposed -> compile -> compute, each clamped to
    the remaining budget."""
    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    clock.advance(1.0)
    led.observe_step(1.0, compute=5.0, comm_exposed=0.8, input_wait=0.5)
    snap = led.snapshot()
    assert snap["phases"]["input_wait"] == pytest.approx(0.5)
    assert snap["phases"]["comm_exposed"] == pytest.approx(0.5)  # clamped
    assert snap["phases"]["compute"] == 0.0  # budget exhausted
    total = sum(snap["phases"].values()) + snap["unattributed_s"]
    assert total == pytest.approx(snap["elapsed_s"])


def test_overattribution_scales_down_to_conserve():
    """Hooks overshooting elapsed (nested spans, clock skew) must not
    break conservation: phases scale down and the overshoot is
    reported."""
    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    clock.advance(4.0)
    led.observe("checkpoint", 3.0)
    led.observe("compile", 3.0)  # 6 s attributed in 4 s of wall
    snap = led.snapshot()
    total = sum(snap["phases"].values()) + snap["unattributed_s"]
    assert total == pytest.approx(snap["elapsed_s"])
    assert snap["overattributed_s"] == pytest.approx(2.0)
    # proportions preserved
    assert snap["phases"]["checkpoint"] == pytest.approx(2.0)
    assert snap["phases"]["compile"] == pytest.approx(2.0)


def test_span_contextmanager_times_into_phase():
    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    with led.span("checkpoint"):
        clock.advance(1.5)
    clock.advance(0.5)
    snap = led.snapshot()
    assert snap["phases"]["checkpoint"] == pytest.approx(1.5)
    assert snap["unattributed_s"] == pytest.approx(0.5)


def test_out_of_step_compile_counter_recovered_from_unattributed():
    """Negotiated-program compile wall that happens between steps
    (eager warmup) is recovered from the hvd_compile_seconds_total
    delta — attributed to 'compile', clamped into unattributed wall."""
    from horovod_tpu.runtime import metrics as M

    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()  # snapshots the counter baseline
    M.counter("hvd_compile_seconds_total").inc(3.0, path="cold")
    clock.advance(10.0)
    snap = led.snapshot()
    assert snap["phases"]["compile"] == pytest.approx(3.0)
    assert snap["unattributed_s"] == pytest.approx(7.0)
    # ...but it can never claim more than the unattributed gap
    M.counter("hvd_compile_seconds_total").inc(100.0, path="cold")
    snap = led.snapshot()
    assert snap["phases"]["compile"] == pytest.approx(10.0)
    assert snap["unattributed_s"] == pytest.approx(0.0)


def test_reform_split_consumes_its_compile_counter_share():
    """Compile seconds inside a re-form are wall already attributed
    under 'reform' — the counter-delta recovery must not claim
    unattributed wall for them a second time."""
    from horovod_tpu.runtime import metrics as M

    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    M.counter("hvd_compile_seconds_total").inc(2.0, path="cold")
    clock.advance(8.0)
    led.observe("reform", 5.0, split={"teardown_s": 1.0,
                                      "compile_s": 2.0,
                                      "resync_s": 2.0})
    snap = led.snapshot()
    assert snap["phases"]["reform"] == pytest.approx(5.0)
    assert snap["phases"]["compile"] == pytest.approx(0.0)  # consumed
    assert snap["unattributed_s"] == pytest.approx(3.0)
    assert snap["reform_split"]["compile_s"] == pytest.approx(2.0)


def test_dominant_bottleneck_names_unattributed_too():
    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    clock.advance(10.0)
    led.observe_step(3.0, compute=3.0, comm_exposed=0.0)
    dom = gp.dominant_bottleneck(led.snapshot())
    assert dom["phase"] == "unattributed"
    assert dom["seconds"] == pytest.approx(7.0)


# ---------------------------------------------------------------------------
# Publication round trip + fleet merge
# ---------------------------------------------------------------------------


def test_publish_and_from_metrics_snapshot_round_trip():
    from horovod_tpu.runtime import metrics as M

    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    clock.advance(4.0)
    led.observe("init", 1.0)
    led.observe_step(2.0, compute=1.5, comm_exposed=0.5)
    led.publish()
    snap = {"meta": {"rank": 3, "host": "h", "time": time.time()},
            "metrics": M.registry().snapshot()}
    rec = gp.from_metrics_snapshot(snap)
    assert rec["rank"] == 3
    assert rec["elapsed_s"] == pytest.approx(4.0)
    assert rec["phases"]["compute"] == pytest.approx(1.5)
    assert rec["unattributed_s"] == pytest.approx(1.0)
    assert rec["goodput_ratio"] == pytest.approx(1.5 / 4.0)
    # the launcher's own snapshot (rank="launcher") is not a ledger
    snap["meta"]["rank"] = "launcher"
    assert gp.from_metrics_snapshot(snap) is None


def _rank_snap(rank, elapsed, phases, unattributed=0.0):
    return {"rank": rank, "elapsed_s": elapsed, "phases": dict(phases),
            "unattributed_s": unattributed,
            "unattributed_ratio": unattributed / elapsed,
            "goodput_ratio": phases.get("compute", 0.0) / elapsed}


def test_fleet_report_names_dominant_bottleneck_with_evidence():
    r0 = _rank_snap(0, 10.0, {"compute": 8.0, "comm_exposed": 1.0,
                              "init": 1.0})
    r1 = _rank_snap(1, 10.0, {"compute": 4.0, "comm_exposed": 5.0,
                              "init": 1.0})
    rep = gp.fleet_report([r1, r0])  # order-independent
    assert rep["world"] == 2
    assert rep["fleet_goodput"] == pytest.approx(12.0 / 20.0)
    dom = rep["dominant_bottleneck"]
    assert dom["phase"] == "comm_exposed"
    assert dom["rank"] == 1
    assert dom["fleet_seconds"] == pytest.approx(6.0)
    assert dom["rank_seconds"] == pytest.approx(5.0)
    line = gp.evidence_line(rep)
    assert "comm_exposed" in line and "rank 1" in line


def test_fleet_window_and_slo_burn_alert():
    clock = _fake_clock()
    fleet = gp.FleetGoodput(slo=0.5, window_s=60.0, clock=clock)
    base = [_rank_snap(0, 100.0, {"compute": 90.0, "comm_exposed": 5.0},
                       unattributed=5.0)]
    rep = fleet.update(base)
    # first sample: cumulative fallback, healthy
    assert rep["window"]["goodput"] == pytest.approx(0.9)
    assert rep["alert"]["firing"] is False
    assert rep["alert"]["reason"] == "none"
    clock.advance(30.0)
    # 30 s later: only 5 of the 30 new seconds were compute, the rest
    # ate comm_exposed -> windowed goodput collapses while cumulative
    # still looks fine
    cur = [_rank_snap(0, 130.0, {"compute": 95.0, "comm_exposed": 30.0},
                      unattributed=5.0)]
    rep = fleet.update(cur)
    assert rep["fleet_goodput"] == pytest.approx(95.0 / 130.0)
    assert rep["window"]["goodput"] == pytest.approx(5.0 / 30.0,
                                                     abs=1e-5)
    dom = rep["window"]["dominant_bottleneck"]
    assert dom["phase"] == "comm_exposed"
    assert dom["rank"] == 0
    assert dom["fleet_seconds"] == pytest.approx(25.0)
    alert = rep["alert"]
    assert alert["firing"] is True
    assert alert["reason"] == "comm_exposed"
    assert alert["burn_rate"] == pytest.approx(
        (1 - 5.0 / 30.0) / (1 - 0.5), abs=1e-3)


def test_fleet_window_trims_history():
    clock = _fake_clock()
    fleet = gp.FleetGoodput(slo=0.0, window_s=10.0, clock=clock)
    for i in range(20):
        fleet.update([_rank_snap(0, 10.0 + i, {"compute": 5.0 + i})])
        clock.advance(5.0)
    # at 5 s cadence and a 10 s window, the deque stays tiny
    assert len(fleet._hist) <= 4


def test_aggregate_render_carries_goodput_and_age_gauges():
    """The launcher merge path: per-rank published snapshots ->
    aggregate /metrics with fleet goodput, bottleneck evidence, the
    SLO alert, and the snapshot-age staleness gauges."""
    from horovod_tpu.runtime import metrics as M

    now = time.time()

    def _metrics_snap(rank, phases, elapsed, age_s):
        series = [{"labels": {"phase": k}, "value": v}
                  for k, v in phases.items()]
        series.append({"labels": {"phase": "unattributed"}, "value": 0.0})
        return json.dumps({
            "meta": {"rank": rank, "host": "h", "time": now - age_s},
            "metrics": {
                "hvd_wallclock_seconds_total": {
                    "kind": "gauge", "series": series},
                "hvd_goodput_elapsed_seconds": {
                    "kind": "gauge",
                    "series": [{"labels": {}, "value": elapsed}]},
                "hvd_goodput_ratio": {
                    "kind": "gauge",
                    "series": [{"labels": {},
                                "value": phases["compute"] / elapsed}]},
            }})

    store = {
        M.INDEX_KEY: json.dumps({"epoch": 1, "size": 2}),
        M._rank_key(1, 0): _metrics_snap(
            0, {"compute": 9.0, "comm_exposed": 1.0}, 10.0, age_s=0.5),
        M._rank_key(1, 1): _metrics_snap(
            1, {"compute": 2.0, "comm_exposed": 8.0}, 10.0, age_s=90.0),
    }
    fleet = gp.FleetGoodput(slo=0.9, window_s=60.0)
    text = M.aggregate_render(store.get, fleet=fleet)
    assert "hvd_goodput_fleet_ratio 0.55" in text
    assert 'hvd_goodput_bottleneck_seconds{phase="comm_exposed",' \
           'rank="1"} 9' in text
    assert 'hvd_goodput_alert{reason="comm_exposed"} 1' in text
    assert "hvd_goodput_burn_rate" in text
    # satellite: a wedged publisher is visible as snapshot age
    ages = {}
    for line in text.splitlines():
        if line.startswith("hvd_metrics_snapshot_age_seconds{"):
            label, val = line.rsplit(" ", 1)
            ages['rank="1"' in label] = float(val)
    assert ages[False] < 30.0      # rank 0 is fresh
    assert ages[True] >= 89.0      # rank 1's publisher is wedged
    assert fleet.last["dominant_bottleneck"]["rank"] == 1


# ---------------------------------------------------------------------------
# data_wait / input starvation
# ---------------------------------------------------------------------------


def test_data_wait_outside_steps_lands_on_ledger():
    import horovod_tpu as hvd

    with hvd.data_wait("unit"):
        time.sleep(0.05)
    snap = gp.ledger().snapshot()
    assert snap["phases"]["input_wait"] >= 0.04


def test_data_wait_noise_floor_filters_short_spans(monkeypatch):
    import horovod_tpu as hvd
    from horovod_tpu.runtime import metrics as M

    monkeypatch.setenv("HOROVOD_DATA_WAIT_MIN_SECONDS", "5")
    before = M.counter("hvd_data_wait_seconds_total").total()
    with hvd.data_wait("filtered"):
        time.sleep(0.01)
    assert M.counter("hvd_data_wait_seconds_total").total() == before
    assert gp.ledger().snapshot().get("phases", {}).get(
        "input_wait", 0.0) == 0.0


def test_input_starvation_dominates_report():
    """The blind-spot scenario: a slow iterator starves fast steps —
    the ledger (not the device observatory) names input_wait."""
    import jax.numpy as jnp

    import horovod_tpu as hvd

    def slow_loader():
        for _ in range(3):
            time.sleep(0.15)
            yield jnp.ones((4,))

    for i, batch in enumerate(hvd.wrap_data_loader(slow_loader(),
                                                   "starved")):
        with hvd.trace_step(step=i):
            (batch * 2).sum().block_until_ready()
    snap = gp.ledger().snapshot()
    assert snap["phases"]["input_wait"] >= 0.4
    assert snap["phases"]["input_wait"] > snap["phases"]["compute"]
    dom = gp.dominant_bottleneck(snap)
    assert dom["phase"] == "input_wait", snap
    rep = gp.fleet_report([snap])
    assert rep["phase_totals"]["input_wait"] >= 0.4


def test_trace_step_splits_in_step_data_wait():
    import horovod_tpu as hvd

    with hvd.trace_step(step=0):
        with hvd.data_wait("in_step"):
            time.sleep(0.1)
        time.sleep(0.05)
    snap = gp.ledger().snapshot()
    assert snap["phases"]["input_wait"] == pytest.approx(0.1, abs=0.05)
    assert snap["phases"]["compute"] == pytest.approx(0.05, abs=0.05)
    total = sum(snap["phases"].values()) + snap["unattributed_s"]
    assert total == pytest.approx(snap["elapsed_s"], rel=0.02)


# ---------------------------------------------------------------------------
# Dumps + CLI
# ---------------------------------------------------------------------------


def test_dump_and_cli_report_round_trip(tmp_path, capsys):
    from horovod_tpu.perf.__main__ import main as perf_main

    for rank, exposed in ((0, 1.0), (1, 6.0)):
        clock = _fake_clock()
        led = gp.GoodputLedger(clock=clock)
        led.start()
        clock.advance(10.0)
        led.observe_step(9.0, compute=9.0 - exposed,
                         comm_exposed=exposed)
        snap = led.snapshot()
        snap["rank"] = rank
        path = tmp_path / f"goodput-r{rank}-g1.json"
        path.write_text(json.dumps(snap))
    rc = perf_main(["goodput", str(tmp_path)])
    human = capsys.readouterr().out
    assert rc == 0
    assert "rank 0" in human and "rank 1" in human
    assert "dominant bottleneck: comm_exposed" in human
    rc = perf_main(["goodput", str(tmp_path), "--json", "--slo",
                    "0.9"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rep["world"] == 2
    assert rep["dominant_bottleneck"]["rank"] == 1
    assert rep["alert"]["firing"] is True
    # empty dir exits 1 (nothing to report is a failure, not a pass)
    empty = tmp_path / "empty"
    empty.mkdir()
    assert perf_main(["goodput", str(empty)]) == 1
    capsys.readouterr()


@pytest.mark.parametrize("form", ["ledger_dump", "metrics_snapshot",
                                  "bench_artifact"])
def test_cli_reads_one_file_of_each_form_it_knows(tmp_path, capsys, form):
    """``perf goodput <file>``: one ledger dump and one ``/metrics.json``
    snapshot are each a rank's ledger.  A ``bench.py`` result line with
    ``extra.goodput`` was a third form until PR 31 removed the script:
    it holds no ledger now, and nothing to report exits 1."""
    from horovod_tpu.perf.__main__ import main as perf_main
    from horovod_tpu.runtime import metrics as M

    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    clock.advance(10.0)
    led.observe("compile", 5.0)
    led.observe_step(2.5, compute=2.5, comm_exposed=0.0)
    if form == "ledger_dump":
        obj = led.snapshot()
    elif form == "metrics_snapshot":
        led.publish()
        obj = {"meta": {"rank": 0, "host": "h", "time": time.time()},
               "metrics": M.registry().snapshot()}
    else:
        obj = {"metric": "m", "value": 1.0, "extra": {
            "goodput_ratio": 0.25,
            "goodput": {"compile_s": 5.0, "compute_s": 2.5,
                        "unattributed_s": 2.5, "elapsed_s": 10.0}}}
    path = tmp_path / "one.json"
    path.write_text(json.dumps(obj))
    rc = perf_main(["goodput", str(path), "--json"])
    rep = json.loads(capsys.readouterr().out)
    if form == "bench_artifact":
        assert rc == 1 and rep["ranks"] == []
        return
    assert rc == 0
    assert rep["ranks"][0]["phases"]["compile"] == pytest.approx(5.0)
    assert rep["dominant_bottleneck"]["phase"] == "compile"


def test_load_snapshots_dedupes_per_generation_dumps(tmp_path):
    """Regression: every elastic re-form's teardown dumps the SAME
    rank's cumulative ledger under a new generation — loading a dump
    dir must keep each rank's newest ledger, not sum the overlapping
    snapshots into a phantom world."""
    for gen, elapsed in ((1, 100.0), (2, 200.0)):
        snap = {"rank": 0, "generation": gen, "elapsed_s": elapsed,
                "phases": {"compute": 0.75 * elapsed},
                "unattributed_s": 0.25 * elapsed,
                "unattributed_ratio": 0.25, "goodput_ratio": 0.75}
        (tmp_path / f"goodput-r0-g{gen}.json").write_text(
            json.dumps(snap))
    snaps = gp.load_snapshots(str(tmp_path))
    assert len(snaps) == 1, snaps
    assert snaps[0]["generation"] == 2
    rep = gp.fleet_report(snaps)
    assert rep["world"] == 1
    assert rep["elapsed_s"] == pytest.approx(200.0)
    assert rep["fleet_goodput"] == pytest.approx(0.75)


def test_fleet_window_label_covers_actual_span():
    """Regression: with sparse updates the retained delta base is
    older than window_s — the reported window seconds must state the
    span the deltas actually cover, not the configured window."""
    clock = _fake_clock()
    fleet = gp.FleetGoodput(slo=0.0, window_s=300.0, clock=clock)
    fleet.update([_rank_snap(0, 100.0, {"compute": 90.0})])
    clock.advance(1200.0)
    rep = fleet.update([_rank_snap(0, 1300.0, {"compute": 1000.0})])
    assert rep["window"]["seconds"] == pytest.approx(1200.0)


def test_ledger_dump_api_writes_named_file(tmp_path):
    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    led.start()
    clock.advance(2.0)
    led.observe("compile", 1.0)
    path = led.dump("unit-test", directory=str(tmp_path))
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path).startswith("goodput-r")
    snaps = gp.load_snapshots(str(tmp_path))
    assert len(snaps) == 1
    assert snaps[0]["phases"]["compile"] == pytest.approx(1.0)
    assert snaps[0]["reason"] == "unit-test"


def test_flight_dump_carries_goodput_event(tmp_path):
    from horovod_tpu.runtime import flight

    flight.reset()
    clock = _fake_clock()
    led = gp.GoodputLedger(clock=clock)
    # swap the global ledger so flight's sys.modules lookup finds it
    gp._ledger = led
    led.start()
    clock.advance(3.0)
    led.observe("compile", 2.0)
    flight.record("unit", x=1)
    out = flight.dump("unit-test", directory=str(tmp_path))
    assert out is not None
    events = [json.loads(line)
              for line in open(out).read().splitlines()[1:]]
    good = [e for e in events if e["kind"] == "goodput"]
    assert good, events
    assert good[0]["compile_s"] == pytest.approx(2.0)
    assert good[0]["elapsed_s"] == pytest.approx(3.0)
    flight.reset()


# ---------------------------------------------------------------------------
# 2-proc acceptance: the fleet report names the straggler's phase+rank
# ---------------------------------------------------------------------------


@pytest.mark.multiprocess
@pytest.mark.slow  # ~25 s fault-injected 2-proc run (ci.sh full suite)
def test_2proc_delay_fault_names_rank1_comm_exposed():
    """Acceptance: with delay@rank1 control-plane faults, the fleet
    goodput report names rank 1 / comm_exposed as the dominant
    bottleneck, and every rank's ledger conserves wall-clock."""
    outs = run_ranks("""
        import json as _json
        for i in range(2):
            with hvd.trace_step(step=i):
                out = hvd.allreduce(jnp.ones((8,)) * (i + 1),
                                    op=hvd.Sum, name="gp%d" % i)
            assert np.allclose(np.asarray(out), 2.0 * (i + 1))
        from horovod_tpu.perf import goodput as gp
        snap = gp.ledger().snapshot()
        tot = sum(snap["phases"].values()) + snap["unattributed_s"]
        assert abs(tot - snap["elapsed_s"]) \\
            <= 0.02 * snap["elapsed_s"] + 1e-6, (tot, snap)
        print("GOODPUT-JSON:" + _json.dumps(snap), flush=True)
    """, extra_env={
        # q-delay makes rank 1 submit late (both ranks wait the round
        # out); the p-delay hits only rank 1's response reads, so its
        # exposed-comm wall is strictly the larger — the evidence the
        # fleet report must surface.
        "HOROVOD_FAULT_SPEC": ("delay@rank1:q/*:0.5s,"
                               "delay@rank1:p/*:0.5s"),
        "HOROVOD_HEARTBEAT_TIMEOUT_SECONDS": "120",
    })
    snaps = []
    for out in outs:
        lines = [ln for ln in out.splitlines()
                 if ln.startswith("GOODPUT-JSON:")]
        assert lines, out
        snaps.append(json.loads(lines[0].split(":", 1)[1]))
    rep = gp.fleet_report(snaps)
    assert rep["world"] == 2
    dom = rep["dominant_bottleneck"]
    assert dom["phase"] == "comm_exposed", rep
    assert dom["rank"] == 1, rep
    by_rank = {s["rank"]: s for s in snaps}
    assert by_rank[1]["phases"]["comm_exposed"] \
        > by_rank[0]["phases"]["comm_exposed"], by_rank
    assert by_rank[1]["phases"]["comm_exposed"] > 0.8
