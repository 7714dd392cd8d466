"""The set-up readers (``benchmark/rings.py`` and the seven metrics under
``benchmark/layers/`` that read a cell's flight rings): on a canned pair
of rings, where every number is known, and on the CPU through a toy
cell, inline and as ``cpu_rank.py``'s launched world.

The toy benchmark is the one ``test_benchmark_harness.py`` stands up,
with the new metrics appended to its manifest the way ``BENCHMARK.json``
has them.  Nothing here loads the TPU library.
"""

import json
import os
import shutil
import sys
import time

import pytest

from benchmark import manifest, rings, run

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "toy")
IN_ALL = ("import_s", "hvd_init_s", "trace_lower_s", "backend_compile_s",
          "cache_load_s", "programs_compiled")
NEW = ("launch_s",) + IN_ALL


def _new_entries() -> list:
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        entries = [dict(m) for m in json.load(f)["per_layer"]
                   if m["name"] in NEW]
    for entry in entries:
        if "workloads" in entry:        # launch_s: the launched cell's
            entry["workloads"] = ["toy-resnet.b8.np2"]
    return entries


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_rings"))
    home = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"), home,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    for kind in ("configs", "traffic", "layers"):
        for name in os.listdir(os.path.join(TOY, kind)):
            shutil.copy(os.path.join(TOY, kind, name),
                        os.path.join(home, kind, name))
    with open(os.path.join(TOY, "BENCHMARK.json"), encoding="utf-8") as f:
        toy = json.load(f)
    toy["per_layer"] += _new_entries()
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(toy, f)
    peaks_path = os.path.join(home, "peaks.json")
    with open(peaks_path, encoding="utf-8") as f:
        peaks = json.load(f)
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e9, "source": "made up for the CPU tests"}
    with open(peaks_path, "w", encoding="utf-8") as f:
        json.dump(peaks, f)
    return root


# ---------------------------------------------------------------------------
# Canned rings
# ---------------------------------------------------------------------------


def _compile(seq, fun_name, trace_s, lower_s, backend_s, cache, wall):
    return {"seq": seq, "mono": wall - 900.0, "wall": wall,
            "kind": "hvd_compile", "ph": "i", "fun_name": fun_name,
            "trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
            "cache": cache, "start_wall": wall - trace_s - lower_s
            - backend_s}


def _rank_ring(started, init_begin, init_s, programs) -> list:
    """A rank's ring: its start, ``hvd_init`` around one phase, and the
    programs it compiled (wall clock from 1000, ``mono`` 900 behind)."""
    def event(seq, wall, kind, ph, **fields):
        return {"seq": seq, "mono": wall - 900.0, "wall": wall,
                "kind": kind, "ph": ph, **fields}
    ring = [
        event(0, started + 0.5, "hvd_process", "i", started_wall=started,
              argv0="-m benchmark.rank"),
        event(1, started + 0.5, "hvd_import", "B", id=1),
        event(2, started + 0.9, "hvd_import", "E", id=1),
        event(3, init_begin, "hvd_init", "B", id=2),
        event(4, init_begin, "hvd_init.backend", "B", id=3, parent=2),
        event(5, init_begin + init_s - 1, "hvd_init.backend", "E", id=3),
        event(6, init_begin + init_s, "hvd_init", "E", id=2),
    ]
    at = init_begin + init_s
    for n, (name, trace_s, lower_s, backend_s, cache) in enumerate(programs):
        at += 2.0
        ring.append(_compile(7 + n, name, trace_s, lower_s, backend_s,
                             cache, at))
    return ring


def _launcher_ring(started) -> list:
    return [
        {"seq": 0, "mono": 100.5, "wall": started + 0.5,
         "kind": "hvd_process", "ph": "i", "started_wall": started,
         "argv0": "-m horovod_tpu.run"},
        {"seq": 1, "mono": 101.0, "wall": started + 1.0,
         "kind": "hvd_launch", "ph": "B", "id": 1, "np": 2},
        {"seq": 2, "mono": 101.1, "wall": started + 1.1,
         "kind": "hvd_launch.spawn", "ph": "B", "id": 2, "parent": 1,
         "rank": 0},
        {"seq": 3, "mono": 101.2, "wall": started + 1.2,
         "kind": "hvd_launch.spawn", "ph": "E", "id": 2, "rank": 0,
         "pid": 7},
        {"seq": 4, "mono": 160.0, "wall": started + 60.0,
         "kind": "hvd_launch", "ph": "E", "id": 1, "np": 2},
    ]


# rank 0 starts first and is slower to import; rank 1 starts last, is
# slower in hvd.init() and compiles one program more, cold
RANK0 = _rank_ring(1002.0, 1006.0, 9.0, [
    ("jit(init)", 0.5, 0.25, 0.125, "hit"),
    ("jit(step)", 3.0, 1.0, 2.0, "hit")])
RANK1 = _rank_ring(1002.5, 1005.5, 11.0, [
    ("jit(init)", 0.25, 0.25, 0.25, "hit"),
    ("jit(step)", 2.0, 1.0, 1.5, "hit"),
    ("jit(allreduce)", 0.5, 0.5, 4.0, "miss")])
LAUNCHER = _launcher_ring(1000.0)
EXPECTED = {"launch_s": 2.5, "import_s": 4.0, "hvd_init_s": 11.0,
            "trace_lower_s": 4.75, "backend_compile_s": 4.0,
            "cache_load_s": 2.125, "programs_compiled": 3}


def _dump(directory: str, name: str, ring: list) -> None:
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, name), "w", encoding="utf-8") as f:
        f.write(json.dumps({"meta": {"events": len(ring)}}) + "\n")
        for event in ring:
            f.write(json.dumps(event) + "\n")


@pytest.fixture()
def canned_world(toy_root):
    """The launched toy cell with the canned rings where a world's
    ``hvdrun --output-filename <out_dir>/ranks`` leaves them."""
    cell = manifest.load_cell("toy-resnet.b8.np2",
                              os.path.join(toy_root, "BENCHMARK.json"))
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    where = os.path.join(cell.out_dir, "ranks", "flight")
    _dump(where, "flight-r0-g0-p10.jsonl", LAUNCHER)
    _dump(where, "flight-r0-g1-p11.jsonl", RANK0)
    _dump(where, "flight-r1-g1-p12.jsonl", RANK1)
    yield cell
    shutil.rmtree(cell.out_dir, ignore_errors=True)


@pytest.mark.parametrize("metric", NEW)
def test_reader_takes_the_worst_rank_of_a_launched_world(canned_world,
                                                         metric):
    """Each reader on the canned world: the value of the rank it is
    largest for (not rank 0's, not a sum over ranks), ``launch_s`` from
    the launcher's start to the last rank's."""
    read = manifest.load_layer_reader(canned_world, metric)
    assert read(None, {}, canned_world) == pytest.approx(EXPECTED[metric])
    assert metric in {m["name"] for m in canned_world.per_layer}


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_none_where_a_ring_lacks_its_hvd_init(canned_world,
                                                           metric):
    """A rank that never entered ``hvd.init()``, and a program that
    records no ``hvd_process`` (the parent of the PR that brought the
    readers): nothing to read, nothing raised."""
    where = os.path.join(canned_world.out_dir, "ranks", "flight")
    no_init = [e for e in RANK1 if not e["kind"].startswith("hvd_init")]
    _dump(where, "flight-r1-g1-p12.jsonl", no_init)
    read = manifest.load_layer_reader(canned_world, metric)
    if metric == "launch_s":      # the processes did start
        assert read(None, {}, canned_world) == pytest.approx(2.5)
    else:
        assert read(None, {}, canned_world) is None
    for name, ring in (("flight-r0-g1-p11.jsonl", RANK0),
                       ("flight-r1-g1-p12.jsonl", RANK1)):
        _dump(where, name, [e for e in ring if e["kind"] != "hvd_process"])
    assert read(None, {}, canned_world) is None
    shutil.rmtree(where)
    assert read(None, {}, canned_world) is None


def test_inline_cell_reads_this_processes_ring(toy_root, monkeypatch):
    """In an inline cell the ring is this process's, found where
    ``scopes.init_spans`` finds it, and written out for the view by
    hand; ``launch_s`` has no launcher to read."""
    from horovod_tpu.runtime import flight

    cell = manifest.load_cell("toy-lm.s32",
                              os.path.join(toy_root, "BENCHMARK.json"))
    recorder = flight.FlightRecorder(64)
    for event in RANK1:
        fields = {k: v for k, v in event.items()
                  if k not in ("seq", "mono", "wall", "kind", "ph")}
        recorder.record(event["kind"], event["ph"], **fields)
    monkeypatch.setattr(flight, "_recorder", recorder)
    got = {m: manifest.load_layer_reader(cell, m)(None, {}, cell)
           for m in NEW}
    assert got["launch_s"] is None
    assert got["programs_compiled"] == 3
    assert got["trace_lower_s"] == pytest.approx(4.5)
    assert got["backend_compile_s"] == pytest.approx(4.0)
    assert got["cache_load_s"] == pytest.approx(1.75)
    assert got["hvd_init_s"] >= 0 and got["import_s"] > 0
    assert len(rings.dumps_under(cell.out_dir)) == 1
    shutil.rmtree(cell.out_dir, ignore_errors=True)


def test_view_by_hand_orders_programs_and_prints_the_residue(canned_world,
                                                             capsys):
    with open(os.path.join(canned_world.out_dir, "records.json"), "w",
              encoding="utf-8") as f:
        json.dump([{"end_to_end": {"setup_s": 30.0}},
                   {"end_to_end": {"setup_s": 40.0}}], f)
    assert rings.main([canned_world.out_dir]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["setup_s"] == 40.0 and view["launch_s"] == 2.5
    launcher, rank0, rank1 = view["processes"]
    assert launcher["launcher"] and launcher["spans"] == [
        ["hvd_launch", pytest.approx(59.0)],
        ["hvd_launch.spawn", pytest.approx(0.1)]]
    assert [p[0] for p in rank1["programs"]] == [
        "jit(allreduce)", "jit(step)", "jit(init)"]
    assert rank1["programs"][0][1:] == [0.5, 0.5, 4.0, "miss"]
    assert rank1["compiled_after_setup"] == []
    # 40 - (2.5 launch + 3 import + 11 init + 10.25 compiling)
    assert rank1["setup_s_less_records"] == pytest.approx(13.25)
    assert rank0["spans"] == [["hvd_import", pytest.approx(0.4)],
                              ["hvd_init.backend", pytest.approx(8.0)]]


# ---------------------------------------------------------------------------
# Through the program, on the CPU
# ---------------------------------------------------------------------------


def _run_cell(toy_root, capfd, workload: str) -> tuple:
    import horovod_tpu as hvd
    from horovod_tpu.runtime import flight

    path = os.path.join(toy_root, "BENCHMARK.json")
    patch = pytest.MonkeyPatch()
    patch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    # a run is a process of its own: its ring opens with its start and
    # its hvd.init() brings a world up (this worker's ring may have been
    # reset, and its world left up, by the files it ran before)
    if hvd.is_initialized():
        hvd.shutdown()
    patch.setattr(flight, "_recorder", flight.FlightRecorder(4096))
    flight.record_process()
    try:
        code = run.run(workload, 0, 0.5, True, time.time(),
                       manifest_path=path, allow_cpu=True,
                       rank_command=(sys.executable,
                                     os.path.join(HERE, "cpu_rank.py")))
    finally:
        patch.undo()
        if hvd.is_initialized():
            hvd.shutdown()
    out = capfd.readouterr().out
    return (code, json.loads(out.strip().splitlines()[-1]),
            manifest.load_cell(workload, path))


@pytest.mark.parametrize("workload", ["toy-lm.s32", "toy-resnet.b8.np2"])
def test_traced_line_has_the_set_up_metrics(toy_root, capfd, workload):
    """A traced run of a toy cell prints every new metric of the cell,
    from this process's ring in the inline cell and from the two ranks'
    dumped rings in ``cpu_rank.py``'s launched world; what they say
    holds together with the harness's own counters."""
    code, line, cell = _run_cell(toy_root, capfd, workload)
    assert code == 0 and line["correct"] is True
    launched = cell.job["launcher"] == "hvdrun"
    metrics = {name: got["value"] for name, got in line["metrics"].items()}
    assert set(IN_ALL) <= set(metrics)
    assert ("launch_s" in metrics) == launched
    assert metrics["programs_compiled"] >= 3
    assert metrics["trace_lower_s"] > 0
    assert metrics["backend_compile_s"] + metrics["cache_load_s"] > 0
    for name in NEW:
        if name in metrics:
            assert metrics[name] >= 0
            assert line["metrics"][name]["unit"] == next(
                m["unit"] for m in cell.per_layer if m["name"] == name)
    found = rings.dumps_under(cell.out_dir)
    if launched:
        # two ranks and the launcher, each ring whole
        assert len(found) == 3
        assert sum(map(rings.is_launcher, found)) == 1
        for ring in found:
            if not rings.is_launcher(ring):
                assert rings.import_s(ring) > 0
                assert rings.hvd_init_s(ring) > 0
        # the phases make up what the harness's clock says of the start
        whole = (metrics["launch_s"] + metrics["import_s"]
                 + metrics["hvd_init_s"])
        assert whole == pytest.approx(metrics["init_s"], abs=1.5)
        assert rings.main([cell.out_dir]) == 0
    else:
        assert len(found) == 1
