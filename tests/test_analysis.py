"""Invariant lint suite (docs/analysis.md).

Acceptance bar of the analysis PR:
  * per-rule positive/negative fixtures — every violating
    program/tree is FLAGGED and its compliant twin passes (a checker
    that can't fail is worse than the regexes it replaced);
  * the HLO parser reads real lowered text (shapes, replica groups,
    permute pairs, tuple types) and refuses unparseable instruction
    lines instead of skipping them;
  * allowlist round trip: mandatory justifications, glob matching,
    stale-entry reporting;
  * ``--json`` schema stability (ci tooling parses it);
  * the REAL tree is green: knobs/concurrency/hlo passes on this
    checkout produce zero non-allowlisted findings — the standing
    regression test for every knob-drift fix this PR made;
  * handshake/cache-key regressions for those fixes: the hierarchical
    and ragged knobs now ride round0_cfg (and through it the AOT
    cache key), and config.is_set distinguishes explicit settings.
"""

import json
import os
import textwrap

import pytest

from horovod_tpu.analysis import PASSES, allowlist as AL
from horovod_tpu.analysis import hlo_lint as HL
from horovod_tpu.analysis import knob_lint as KL
from horovod_tpu.analysis import concurrency_lint as CL
from horovod_tpu.analysis.__main__ import main as cli_main
from horovod_tpu.analysis.findings import Finding, sort_findings

DATA = os.path.join(os.path.dirname(__file__), "data", "analysis")


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

_REAL_SNIPPET = """\
HloModule jit_fn

region_0.4 {
  Arg_0.5 = f32[] parameter(0)
  Arg_1.6 = f32[] parameter(1)
  ROOT add.7 = f32[] add(Arg_0.5, Arg_1.6)
}

ENTRY main.30 {
  Arg_0.1 = f32[8,1024]{1,0} parameter(0)
  reshape.55 = f32[1024]{0} reshape(Arg_0.1)
  reduce-scatter.56 = f32[256]{0} reduce-scatter(reshape.55), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, dimensions={0}, to_apply=region_0.4
  all-reduce.75 = s8[1,256]{1,0} all-reduce(reduce-scatter.56), channel_id=3, replica_groups={{0,4},{1,5},{2,6},{3,7}}, use_global_device_ids=true, to_apply=region_0.4
  collective-permute.9 = f32[1]{0} collective-permute(reshape.55), channel_id=4, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  tuple.10 = (f32[256]{0}, s32[16]{0}) tuple(reduce-scatter.56, reduce-scatter.56)
  ROOT all-gather.83 = f32[1024]{0} all-gather(reduce-scatter.56), channel_id=5, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, use_global_device_ids=true
}
"""


def test_parser_reads_real_shapes_and_groups():
    prog = HL.parse_hlo(_REAL_SNIPPET)
    by_name = {i.name: i for i in prog.instructions}
    rs = by_name["reduce-scatter.56"]
    assert rs.opcode == "reduce-scatter"
    assert rs.shapes == (HL.Shape("f32", (256,)),)
    assert rs.replica_groups == ((0, 1, 2, 3), (4, 5, 6, 7))
    ar = by_name["all-reduce.75"]
    assert ar.shapes[0].dtype == "s8"
    assert ar.replica_groups == ((0, 4), (1, 5), (2, 6), (3, 7))
    cp = by_name["collective-permute.9"]
    assert cp.source_target_pairs == ((0, 1), (1, 2), (2, 3), (3, 0))
    # tuple result types flatten into multiple shapes
    assert by_name["tuple.10"].shapes == (HL.Shape("f32", (256,)),
                                          HL.Shape("s32", (16,)))
    # scalars parse as dims ()
    assert by_name["Arg_0.5"].shapes[0].dims == ()
    assert len(prog.collectives()) == 4


def test_parser_refuses_garbled_instruction():
    with pytest.raises(ValueError, match="no opcode"):
        HL.parse_hlo("  x.1 = f32[4]{0} \n")


def test_group_axis_kinds():
    assert HL.group_axis_kind([(0, 1, 2, 3), (4, 5, 6, 7)], 4) == "local"
    assert HL.group_axis_kind([(0, 4), (1, 5), (2, 6), (3, 7)], 4) == \
        "cross"
    assert HL.group_axis_kind([(0, 1, 2, 3, 4, 5, 6, 7)], 4) == "world"
    assert HL.group_axis_kind([(0, 1), (2, 5)], 2) == "mixed"
    assert HL.permute_axis_kind([(0, 1), (1, 0)], 4) == "local"
    assert HL.permute_axis_kind([(0, 4), (4, 0)], 4) == "cross"
    assert HL.permute_axis_kind([(0, 1), (0, 4)], 4) == "mixed"


# ---------------------------------------------------------------------------
# Rules: violating program flagged, compliant twin passes
# ---------------------------------------------------------------------------


def _hlo(body: str) -> str:
    return "ENTRY main {\n" + textwrap.dedent(body) + "}\n"


def test_no_full_buffer_flags_any_spelling():
    bad_1d = _hlo("  x.1 = f32[384]{0} broadcast(y.0), dimensions={0}\n")
    bad_2d = _hlo("  x.1 = f32[4,96]{1,0} concatenate(y.0), dimensions={0}\n")
    good = _hlo("  x.1 = f32[96]{0} broadcast(y.0), dimensions={0}\n")
    rule = [HL.no_full_buffer(384)]
    assert {f.rule for f in HL.check_program(bad_1d, rule)} == \
        {"HLO-FULLBUF"}
    # the 2-D respelling the old regex could never see
    assert HL.check_program(bad_2d, rule), "2-D spelling not flagged"
    assert HL.check_program(good, rule) == []


def test_no_full_buffer_exempts_global_view_boundary():
    # jit entry params and SPMD shard/unshard calls print GLOBAL shapes
    # (8 ranks x 48 = 384 total) — per-device they are 1/N shards
    text = _hlo(
        '  Arg_0.1 = f32[8,48]{1,0} parameter(0)\n'
        '  custom-call.2 = f32[8,48]{1,0} custom-call(Arg_0.1), '
        'custom_call_target="Sharding", sharding={devices=[8,1]<=[8]}\n'
        '  custom-call.3 = f32[1,48]{1,0} custom-call(custom-call.2), '
        'custom_call_target="SPMDFullToShardShape", sharding={manual}\n')
    assert HL.check_program(text, [HL.no_full_buffer(384)]) == []


def test_min_and_no_collective_rules():
    mono = _hlo(
        "  ar.1 = f32[64]{0} all-reduce(x.0), replica_groups={{0,1}}, "
        "to_apply=region_0.4\n")
    ringy = _hlo("".join(
        f"  cp.{i} = f32[8]{{0}} collective-permute(x.0), "
        "source_target_pairs={{0,1},{1,0}}\n" for i in range(3)))
    assert HL.check_program(mono, HL.overlap_rules(1)) != []
    assert {f.rule for f in HL.check_program(mono, HL.overlap_rules(1))} \
        == {"HLO-BUCKETS", "HLO-MONOLITHIC"}
    assert HL.check_program(ringy, HL.overlap_rules(3)) == []
    assert HL.check_program(ringy, [HL.min_collectives(
        "collective-permute", 4)]) != []


def test_lossy_cross_only_rule():
    local = ("replica_groups={{0,1,2,3},{4,5,6,7}}, "
             "use_global_device_ids=true, to_apply=r")
    cross = ("replica_groups={{0,4},{1,5},{2,6},{3,7}}, "
             "use_global_device_ids=true, to_apply=r")
    world = ("replica_groups={{0,1,2,3,4,5,6,7}}, "
             "use_global_device_ids=true, to_apply=r")
    ok = _hlo(f"  a.1 = s8[1,256]{{1,0}} all-reduce(x.0), {cross}\n"
              f"  b.2 = f32[256]{{0}} reduce-scatter(y.0), {local}\n")
    bad_local = _hlo(f"  a.1 = s8[1,256]{{1,0}} all-reduce(x.0), {local}\n")
    bad_world = _hlo(f"  a.1 = s8[1,256]{{1,0}} all-reduce(x.0), {world}\n")
    bad_idx = _hlo(f"  a.1 = s32[16]{{0}} all-gather(x.0), {local}\n")
    cast_ok = _hlo(f"  a.1 = f16[256]{{0}} reduce-scatter(x.0), {local}\n")
    rules = HL.hierarchical_lossy_rules(4)
    assert HL.check_program(ok, rules) == []
    assert HL.check_program(bad_local, rules) != []
    assert HL.check_program(bad_world, rules) != []
    assert HL.check_program(bad_idx, rules) != []
    # fp16/bf16 CASTS run every hop at wire width by design (PR 10)
    assert HL.check_program(cast_ok, rules) == []


def test_single_fused_kernel_rule():
    fused = _hlo('  k.1 = (f32[128]{0}, f32[128]{0}) custom-call(a.0), '
                 'custom_call_target="tpu_custom_call", '
                 'api_version=API_VERSION_STATUS_RETURNING\n')
    chain = _hlo("  m.1 = f32[128]{0} multiply(a.0, b.0)\n"
                 "  s.2 = f32[128]{0} subtract(m.1, c.0)\n")
    assert HL.check_program(fused, [HL.single_fused_kernel(1)]) == []
    assert HL.check_program(chain, [HL.single_fused_kernel(1)]) != []
    assert HL.check_program(fused, [HL.single_fused_kernel(2)]) != []


def test_check_file_directives(tmp_path):
    findings = HL.check_file(os.path.join(DATA, "bad_zero2.hlo"))
    assert {f.rule for f in findings} == {"HLO-FULLBUF", "HLO-BUCKETS"}
    nodirectives = tmp_path / "x.hlo"
    nodirectives.write_text("ENTRY main {\n}\n")
    with pytest.raises(ValueError, match="no '// hvd-lint"):
        HL.check_file(str(nodirectives))


# ---------------------------------------------------------------------------
# knob lint
# ---------------------------------------------------------------------------


def test_scan_env_reads_patterns(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text(textwrap.dedent("""\
        import os
        _KEY = "HOROVOD_INDIRECT"
        a = os.environ.get("HOROVOD_A")
        b = os.getenv("HOROVOD_B", "0")
        c = os.environ["HOROVOD_C"]
        d = "HOROVOD_D" in os.environ
        e = os.environ.get(_KEY)
        os.environ["HOROVOD_WRITE"] = "1"          # write: exempt
        os.environ.setdefault("HOROVOD_SETDEF", "2")  # guarded write
        f = os.environ.get("NOT_HOROVOD")          # other namespaces
    """))
    names = sorted(n for _, n in KL.scan_env_reads(str(mod)))
    assert names == ["HOROVOD_A", "HOROVOD_B", "HOROVOD_C",
                     "HOROVOD_D", "HOROVOD_INDIRECT"]


def test_knob_fixture_tree_flagged_and_twin_passes(tmp_path):
    bad = KL.run(package_dir=os.path.join(DATA, "bad_knobs"))
    assert {f.rule for f in bad} == {"KNOB-RAW-ENV"}
    assert any("HOROVOD_NOT_A_KNOB" in f.message for f in bad)
    assert any("HOROVOD_ALSO_NOT_A_KNOB" in f.message for f in bad)
    twin = tmp_path / "clean"
    twin.mkdir()
    (twin / "ok.py").write_text(
        "import os\n"
        "from horovod_tpu.common import config\n"
        "def f():\n"
        "    os.environ['HOROVOD_OVERLAP'] = '1'\n"
        "    return config.get('overlap')\n")
    assert KL.run(package_dir=str(twin)) == []


def test_knob_dead_rule_flags_readerless_knob(monkeypatch):
    """KNOB-DEAD regression (the HOROVOD_EAGER_PAD_POW2 class): a
    registered knob no string in the package names is
    documentation fiction with a CLI flag — register a fake one and
    the rule must flag exactly it."""
    from horovod_tpu.common import config as _cfg

    fake = dict(_cfg._KNOBS)
    fake["phantom_knob"] = _cfg.Knob(
        "HOROVOD_PHANTOM_KNOB", 0, int,
        help="must agree on every rank (validated at the round-0 "
             "handshake).")          # marker also exercises rule (4)
    monkeypatch.setattr(_cfg, "_KNOBS", fake)
    findings = KL.run()
    dead = [f for f in findings if f.rule == "KNOB-DEAD"]
    assert any("phantom_knob" in f.message for f in dead)
    # and only the phantom: the real registry has no dead knobs
    assert all("phantom_knob" in f.message for f in dead)


def test_real_tree_knobs_green_after_allowlist():
    """THE standing regression for every knob-drift fix this PR made:
    raw reads routed/justified, hierarchical+ragged knobs in the
    handshake, help markers in sync, cache keys covered or justified,
    every knob documented."""
    findings = KL.run()
    entries = AL.load(AL.default_path())
    active, covered, _ = AL.split(findings, entries)
    assert active == [], "\n".join(f.render() for f in active)
    # the allowlist is load-bearing, not decorative
    assert covered, "expected justified allowlisted findings"


# ---------------------------------------------------------------------------
# concurrency lint
# ---------------------------------------------------------------------------


def test_lock_fixture_tree_flagged():
    findings = CL.run(package_dir=os.path.join(DATA, "bad_locks"))
    rules = {f.rule for f in findings}
    assert rules == {"CONC-LOCK-ORDER", "CONC-SIGNAL-LOCK",
                     "CONC-BLOCKING-UNDER-LOCK"}
    # the blocking rule is TRANSITIVE: the sleep() two call hops below
    # deep_block_under_lock's critical section is reported too
    deep = [f for f in findings
            if f.rule == "CONC-BLOCKING-UNDER-LOCK"
            and "_outer_helper" in f.message]
    assert deep and all("sleep" in f.message for f in deep)


def test_lock_compliant_twin_passes(tmp_path):
    twin = tmp_path / "clean"
    twin.mkdir()
    (twin / "ok.py").write_text(textwrap.dedent("""\
        import signal
        import threading
        import time

        _lock_a = threading.Lock()
        _lock_b = threading.Lock()
        _ring = threading.RLock()

        def a_then_b():
            with _lock_a:
                with _lock_b:
                    return 1

        def also_a_then_b():
            with _lock_a:
                with _lock_b:
                    return 2

        def _handler(signum, frame):
            with _ring:        # RLock: signal-safe by the PR 8 fix
                return None

        def install():
            signal.signal(signal.SIGTERM, _handler)

        def sleep_outside_lock():
            with _lock_a:
                x = 1
            time.sleep(0.01)
            return x
    """))
    assert CL.run(package_dir=str(twin)) == []


def test_real_tree_concurrency_green():
    assert CL.run() == []


def test_signal_handler_reaches_flight_ring():
    """The PR 8 bug class stays DETECTABLE on the real tree: the
    fatal-signal handler's static call graph must reach
    FlightRecorder.record — if resolution loses that edge, reverting
    the ring to a plain Lock would go unflagged."""
    from horovod_tpu.analysis import repo_root

    root = repo_root()
    rels = []
    for sub in CL.SCAN_DIRS:
        base = os.path.join(root, "horovod_tpu", sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "csrc")]
            rels += [os.path.relpath(os.path.join(dirpath, f), root)
                     for f in filenames if f.endswith(".py")]
    auditor = CL.Auditor(root, rels)
    flight = "horovod_tpu/runtime/flight.py"
    reach = auditor._reachable((flight, "", "_on_fatal_signal"))
    assert (flight, "FlightRecorder", "record") in reach
    ring = auditor.locks[(flight, "FlightRecorder", "_lock")]
    assert ring.kind == "RLock"


# ---------------------------------------------------------------------------
# hlo pass on the real lowered program set
# ---------------------------------------------------------------------------


def test_hlo_pass_clean_on_real_programs():
    """The CPU-lowered program set (ZeRO-2/3, overlap, hierarchical
    int8/topk) passes every preset, and the embedded positive controls
    prove the rules still fire (a broken checker fails HLO-SELFCHECK
    here, not silently)."""
    from horovod_tpu.analysis import programs

    assert programs.run() == []


# ---------------------------------------------------------------------------
# allowlist + CLI
# ---------------------------------------------------------------------------


def test_allowlist_round_trip(tmp_path):
    path = tmp_path / "al.json"
    entries = [AL.Entry(rule="KNOB-RAW-ENV", location="pkg/a.py:*",
                        justification="because reasons",
                        match="HOROVOD_X")]
    path.write_text(json.dumps(
        {"schema": 1, "entries": [e.to_dict() for e in entries]}))
    loaded = AL.load(str(path))
    assert loaded == entries
    f_hit = Finding(rule="KNOB-RAW-ENV", severity="error",
                    location="pkg/a.py:12", message="raw HOROVOD_X read")
    f_miss = Finding(rule="KNOB-RAW-ENV", severity="error",
                     location="pkg/b.py:3", message="raw HOROVOD_X read")
    active, covered, used = AL.split([f_hit, f_miss], loaded)
    assert covered == [f_hit] and active == [f_miss] and used == {0}
    assert AL.stale_entries(loaded, set()) == loaded


def test_allowlist_requires_justification(tmp_path):
    path = tmp_path / "al.json"
    path.write_text(json.dumps({"schema": 1, "entries": [
        {"rule": "X", "location": "*", "justification": "  "}]}))
    with pytest.raises(AL.AllowlistError, match="no justification"):
        AL.load(str(path))
    path.write_text(json.dumps({"schema": 2, "entries": []}))
    with pytest.raises(AL.AllowlistError, match="schema"):
        AL.load(str(path))


def test_repo_allowlist_every_entry_used():
    """Zero unexplained AND zero stale entries: every entry in the
    checked-in allowlist still matches a real finding from SOME pass
    (all three run here — an entry excusing an hlo finding must not
    read as stale just because the cheap passes can't see it; the
    stale rule keeps the file shrink-only)."""
    from horovod_tpu.analysis import programs

    entries = AL.load(AL.default_path())
    findings = KL.run() + CL.run() + programs.run()
    _active, _covered, used = AL.split(findings, entries)
    stale = AL.stale_entries(entries, used)
    assert stale == [], [e.to_dict() for e in stale]


def test_cli_exit_codes_and_json_schema(capsys):
    rc = cli_main(["knobs", "--package-dir",
                   os.path.join(DATA, "bad_knobs"), "--json",
                   "--no-allowlist"])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out)
    assert doc["schema"] == 1
    assert doc["passes"] == ["knobs"]
    assert doc["summary"]["active"] == 2
    assert doc["summary"]["total"] == doc["summary"]["active"] + \
        doc["summary"]["allowlisted"]
    for f in doc["findings"]:
        assert set(f) == {"rule", "severity", "location", "message",
                          "fix_hint", "pass", "allowlisted"}
    # unknown pass name -> usage error
    assert cli_main(["nonsense"]) == 2
    capsys.readouterr()


def test_cli_green_on_real_tree(capsys):
    """`python -m horovod_tpu.analysis knobs concurrency` exits 0 on
    this checkout (the ci.sh quick-path stage in-process)."""
    rc = cli_main(["knobs", "concurrency"])
    capsys.readouterr()
    assert rc == 0


def test_pass_registry_complete():
    assert set(PASSES) == {"hlo", "knobs", "concurrency"}


# ---------------------------------------------------------------------------
# handshake/cache-key regressions for the knob-lint fixes
# ---------------------------------------------------------------------------


def test_round0_cfg_carries_hierarchical_and_ragged(monkeypatch):
    """The KNOB-TRACE-SEMANTICS fixes: the hierarchical topology and
    ragged strategy knobs now ride the round-0 handshake, so a
    divergence fails fast instead of deadlocking in mismatched
    collectives."""
    from horovod_tpu.runtime import controller as ctl

    for env in ("HOROVOD_HIERARCHICAL_ALLREDUCE",
                "HOROVOD_HIERARCHICAL_ALLGATHER",
                "HOROVOD_HIERARCHICAL_LOCAL_SIZE",
                "HOROVOD_RAGGED_ALLGATHER"):
        monkeypatch.delenv(env, raising=False)
    base = ctl.round0_cfg()
    assert len(base) == len(ctl.ROUND0_KNOB_ENVS)
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    assert ctl.round0_cfg() != base
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_LOCAL_SIZE", "4")
    with_ls = ctl.round0_cfg()
    assert with_ls != base and with_ls[17] == 4
    monkeypatch.delenv("HOROVOD_HIERARCHICAL_ALLREDUCE")
    # local size is normalized to 0 while no hierarchical mode is on
    # (same idiom as quant_block_size under compression=none)
    assert ctl.round0_cfg() == base
    monkeypatch.setenv("HOROVOD_RAGGED_ALLGATHER", "psum")
    assert ctl.round0_cfg() != base
    monkeypatch.setenv("HOROVOD_RAGGED_ALLGATHER", "pad")
    assert ctl.round0_cfg()[18] == 2
    monkeypatch.setenv("HOROVOD_RAGGED_ALLGATHER", "tyop")
    assert ctl.round0_cfg()[18] >= 256  # typo still trips the mismatch


def test_round0_cfg_feeds_aot_cache_key(monkeypatch):
    """The cache-key half of the same fix: the AOT cache keys on
    round0_cfg() by construction, so toggling a newly-handshaken knob
    invalidates persisted programs too."""
    from horovod_tpu.runtime import aot_cache

    monkeypatch.delenv("HOROVOD_HIERARCHICAL_ALLREDUCE", raising=False)
    base = aot_cache._cfg_vector()
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    assert aot_cache._cfg_vector() != base


def test_round0_mismatch_message_derived_from_vector():
    """The diagnostic lists exactly the knobs the vector validates —
    built from ROUND0_KNOB_ENVS, so it can never drift again."""
    from horovod_tpu.common import config as _cfg
    from horovod_tpu.runtime import controller as ctl

    envs = {k.env for k in _cfg.knobs().values()}
    assert set(ctl.ROUND0_KNOB_ENVS) <= envs
    assert "HOROVOD_HIERARCHICAL_ALLREDUCE" in ctl.ROUND0_KNOB_ENVS
    assert "HOROVOD_RAGGED_ALLGATHER" in ctl.ROUND0_KNOB_ENVS


def test_config_is_set(monkeypatch):
    from horovod_tpu.common import config

    monkeypatch.delenv("HOROVOD_ZERO_STAGE", raising=False)
    assert not config.is_set("zero_stage")
    monkeypatch.setenv("HOROVOD_ZERO_STAGE", "")
    assert not config.is_set("zero_stage")
    # whitespace-only == unset: get() falls back to the default for
    # it, and checkpoint's stage-3 residency guard must not treat it
    # as an explicit stage choice
    monkeypatch.setenv("HOROVOD_ZERO_STAGE", "  ")
    assert not config.is_set("zero_stage")
    monkeypatch.setenv("HOROVOD_ZERO_STAGE", "2")
    assert config.is_set("zero_stage")


def test_findings_sort_and_render():
    a = Finding(rule="B-RULE", severity="warning", location="x:1",
                message="w")
    b = Finding(rule="A-RULE", severity="error", location="y:2",
                message="e", fix_hint="do it")
    assert sort_findings([a, b]) == [b, a]
    assert "fix: do it" in b.render()
    with pytest.raises(ValueError, match="severity"):
        Finding(rule="X", severity="meh", location="z", message="m")
