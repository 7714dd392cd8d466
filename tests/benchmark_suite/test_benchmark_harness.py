"""The benchmark's harness on the CPU at toy sizes.

``toy_root`` stands a whole benchmark up in a temporary directory: a copy
of ``benchmark/`` with two toy configurations, three traffic files and a
per-layer metric dropped in as new files, and a manifest that names
them.  No code is edited to make them found, which is the property a
later PR that adds a cell relies on.  The CPU is allowed through a
function argument only the tests pass; the command line cannot.

Nothing here loads the TPU library, while a module is imported or after.
"""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import manifest, run

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "toy")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_benchmark"))
    home = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"), home,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    for kind in ("configs", "traffic", "layers"):
        for name in os.listdir(os.path.join(TOY, kind)):
            shutil.copy(os.path.join(TOY, kind, name),
                        os.path.join(home, kind, name))
    shutil.copy(os.path.join(TOY, "BENCHMARK.json"), root)
    peaks_path = os.path.join(home, "peaks.json")
    with open(peaks_path, encoding="utf-8") as f:
        peaks = json.load(f)
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e9, "source": "made up for the CPU tests"}
    with open(peaks_path, "w", encoding="utf-8") as f:
        json.dump(peaks, f)
    return root


def run_cell(toy_root, capfd, workload: str, trace: bool) -> tuple:
    """One run of a toy cell on the CPU: (exit code, last line, cell)."""
    import horovod_tpu as hvd

    path = os.path.join(toy_root, "BENCHMARK.json")
    patch = pytest.MonkeyPatch()
    # for the ranks of a launched world: one device each (this process
    # opened its backend long ago)
    patch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    try:
        code = run.run(workload, 0, 0.5, trace, time.time(),
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


@pytest.mark.parametrize("workload", ["toy-lm.s32", "toy-resnet.b8",
                                      "toy-resnet.b8.np2"])
def test_untraced_line_is_the_contracts(toy_root, capfd, workload):
    """Exactly the contract's keys, the cell's end-to-end metrics, and a
    system that agrees with its plain reference at toy size (both
    families; the launched world also holds the first update to the mean
    of its ranks' reference gradients)."""
    code, line, cell = run_cell(toy_root, capfd, workload, False)
    assert code == 0
    assert set(line) == LINE_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["count"] >= cell.chips
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for metric in cell.end_to_end:
        got = line["metrics"][metric["name"]]
        assert set(got) == {"value", "unit"}
        assert got["unit"] == metric["unit"] and got["value"] > 0
    with open(os.path.join(cell.out_dir, "records.json")) as f:
        records = json.load(f)
    assert len(records) == cell.job["world"]
    for record in records:
        assert record["reference"]["ok"], record["reference"]
        assert record["compiles_in_window"] == 0
    # mfu x peak / model FLOPs reproduces the cell's throughput
    family = manifest.load_family(cell)
    flops = family.model_flops_per_sample(cell.config, cell.job)
    throughput = line["metrics"][
        cell.config["sample"]["throughput_metric"]]["value"]
    units = cell.job.get("seq", 1)
    assert line["metrics"]["mfu"]["value"] * 1e12 / flops * units \
        == pytest.approx(throughput, rel=1e-9)


@pytest.mark.parametrize("workload", ["toy-lm.s32", "toy-resnet.b8.np2"])
def test_traced_line_has_the_per_layer_metrics(toy_root, capfd, workload):
    code, line, cell = run_cell(toy_root, capfd, workload, True)
    assert code == 0
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    # every per-layer metric of the cell, the dropped-in one among them
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer}
    assert "steps_in_window" in line["metrics"]
    assert line["metrics"]["steps_in_window"]["value"] == line["attempted"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    if cell.job["world"] > 1:     # a real collective ran on the CPU ranks
        assert line["metrics"]["coll_s_per_step"]["value"] > 0


def test_new_files_are_found_by_name(toy_root):
    """A workload, a configuration and a per-layer metric that exist
    only as files of the temporary benchmark and entries of its
    manifest."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    cell = manifest.load_cell("toy-lm.s32", path)
    assert cell.config["name"] == "toy-lm" and cell.job["seq"] == 32
    assert cell.chips == 1
    assert "tokens_per_s_per_chip" in {m["name"] for m in cell.end_to_end}
    assert "images_per_s_per_chip" not in {m["name"]
                                           for m in cell.end_to_end}
    read = manifest.load_layer_reader(cell, "steps_in_window")
    assert read(None, {"chunk_walls": [1.0, 1.0], "chunk_steps": 3},
                cell) == 6
    assert hasattr(manifest.load_family(cell), "Trainer")
    with pytest.raises(KeyError, match="not in the manifest"):
        manifest.load_cell("no-such-cell", path)
    with pytest.raises(KeyError, match="not in peaks.json"):
        manifest.load_peaks(cell, "TPU v9")


def test_every_cell_of_the_manifest_resolves():
    """The real manifest: each workload's files exist, every per-layer
    metric has its reader, and each names metrics the cell reports."""
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        listed = json.load(f)
    chips = {w["name"]: w["chips"] for w in listed["workloads"]}
    assert chips["resnet50.b256.np4"] == 4       # the one world of ranks
    assert chips["resnet50.b256.1chip"] == chips["gpt2-124m.s1024"] \
        == chips["gpt2-124m.s8192"] == 1
    for workload in listed["workloads"]:
        cell = manifest.load_cell(workload["name"])
        family = manifest.load_family(cell)
        assert family.model_flops_per_sample(cell.config, cell.job) > 0
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.config["sample"]["throughput_metric"] in names
        assert cell.per_layer
        for metric in cell.per_layer:
            assert metric["moves"] in names
            assert callable(manifest.load_layer_reader(cell, metric["name"]))


@pytest.mark.parametrize("path", [manifest.MANIFEST,
                                  os.path.join(TOY, "BENCHMARK.json")],
                         ids=["real", "toy"])
def test_manifest_keeps_the_contracts_limits(path):
    """What the driver refuses before any run: a unit, a name or a
    ``why`` outside the contract's alphabet and length, a metric that is
    named twice, a per-layer metric where the metric it moves is not."""
    with open(path, encoding="utf-8") as f:
        listed = json.load(f)
    assert os.path.getsize(path) <= 64 * 1024
    assert set(listed) == {"command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
    metrics = listed["end_to_end"] + listed["per_layer"]
    named = listed["configs"] + listed["workloads"] + metrics
    assert len({e["name"] for e in named}) == len(named)
    for entry in named:
        assert name.fullmatch(entry["name"]), entry["name"]
        assert len(entry.get("why", "")) <= 200, entry["name"]
    for metric in metrics:
        assert unit.fullmatch(metric["unit"]), (metric["name"],
                                                metric["unit"])
    cells = {w["name"] for w in listed["workloads"]}
    reported = {m["name"]: set(m.get("workloads", cells))
                for m in listed["end_to_end"]}
    for metric in listed["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in listed["per_layer"]:
        assert name.fullmatch(metric["layer"]), metric["layer"]
        assert set(metric.get("workloads", cells)) \
            <= reported[metric["moves"]], metric["name"]
    four = sum(w["chips"] == 4 for w in listed["workloads"])
    assert four <= max(1, len(cells) // 4)


@pytest.mark.parametrize("workload", ["resnet50.b256.1chip",
                                      "resnet50.b256.np4"])
def test_no_tpu_no_result(workload):
    """The command itself, where JAX finds no TPU: another exit code
    than 0 and no result line, inline and through the launcher."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOROVOD_PLATFORM="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        command = json.load(f)["command"]
    done = subprocess.run(
        [sys.executable, *command[1:], "--workload", workload, "--seed",
         "0", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
    assert "no TPU found" in done.stderr + done.stdout


def test_model_flops_equal_hand_worked_values(toy_root):
    """Both families, by hand.  toy-resnet (side 32, width 8, two
    bottlenecks): stem 16*16*49*3*8 = 301,056 MACs; block 0 at side 8:
    4,096 + 36,864 + 16,384 and its projection 16,384; block 1, stride
    2: 32,768 at side 8, then 36,864 + 16,384 + 32,768 at side 4; the
    classifier 640: 494,208 MACs, x 2 FLOPs x 3 passes.  toy-lm (d 32,
    2 layers, d_ff 128, vocab 97, seq 32): 2 * (4*32*32 + 2*32*128) +
    32*97 = 27,680 MACs a token and 2 * 32 * 32*33 = 67,584 for causal
    attention: 6 * (32 * 27,680 + 67,584)."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    cell = manifest.load_cell("toy-resnet.b8", path)
    family = manifest.load_family(cell)
    assert family.forward_macs(cell.config) == 494_208
    assert family.model_flops_per_sample(cell.config, cell.job) == 2_965_248
    cell = manifest.load_cell("toy-lm.s32", path)
    family = manifest.load_family(cell)
    assert family.model_flops_per_sample(cell.config, cell.job) == 5_720_064
    # the published figures of the real configurations
    cell = manifest.load_cell("resnet50.b256.1chip")
    assert manifest.load_family(cell).forward_macs(cell.config) \
        == pytest.approx(4.09e9, rel=2e-3)
    cell = manifest.load_cell("gpt2-124m.s1024")
    assert manifest.load_family(cell).model_flops_per_sample(
        cell.config, cell.job) / 1024 == pytest.approx(0.80e9, rel=5e-3)


def test_kernel_costs_count_what_flash_attention_requires():
    """Seven causal products a layer (two forward, five backward) over
    24 rows x head 64 x 8192 * 8193 / 2 score pairs x 12 layers, 2 FLOPs
    a multiply-accumulate; at this shape the FLOPs bound the roofline."""
    cell = manifest.load_cell("gpt2-124m.s8192")
    costs = manifest.load_family(cell).kernel_costs(cell.config, cell.job)
    assert set(costs) == {"flash_attn"}
    assert costs["flash_attn"]["flops"] == 7 * 12 * 24 * 64 * 8192 * 8193
    peaks = manifest.load_peaks(cell, "TPU v5 lite")
    assert costs["flash_attn"]["flops"] / peaks["bf16_flops_per_s"] \
        > costs["flash_attn"]["bytes"] / peaks["hbm_bytes_per_s"] > 0


def test_weights_come_from_the_programs_initialiser(toy_root):
    """``_DeviceRandn`` stands in for the numpy generator of
    ``init_params``: the same tree, shapes and types, and the scale of
    every leaf, drawn on the device from the seed."""
    import jax

    from horovod_tpu.models import transformer

    cell = manifest.load_cell("toy-lm.s32",
                              os.path.join(toy_root, "BENCHMARK.json"))
    family = manifest.load_family(cell)
    cfg = transformer.TransformerConfig(max_seq=32,
                                        **family._sizes(cell.config))
    want = transformer.init_params(np.random.RandomState(0), cfg)

    def make(seed):
        return jax.jit(lambda key: transformer.init_params(
            family._DeviceRandn(key), cfg))(jax.random.PRNGKey(seed))

    got = make(0)
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert float(a.std()) == pytest.approx(float(b.std()), rel=0.2,
                                               abs=1e-6)
    same, other = make(0), make(1)
    assert np.array_equal(got["embed"], same["embed"])
    assert not np.array_equal(got["embed"], other["embed"])


def test_first_update_check_tells_mean_from_sum():
    cell = manifest.load_cell("resnet50.b256.np4")
    family = manifest.load_family(cell)
    rng = np.random.RandomState(0)
    grads = [rng.randn(10) for _ in range(4)]
    mean = np.mean(grads, axis=0)

    def probes(update, ranks=4):
        """Rank 0's probe carries the reference gradients of all."""
        first = {"lr": 0.01, "update": update.tolist(),
                 "reference_gradients": [g.tolist() for g in grads]}
        return [first] + [{"lr": 0.01, "update": update.tolist()}
                          for _ in range(ranks - 1)]

    assert family.check_first_update(probes(-0.01 * mean))["ok"]
    assert not family.check_first_update(probes(-0.01 * 4 * mean))["ok"]
    assert not family.check_first_update(probes(-0.01 * grads[0]))["ok"]
    assert not family.check_first_update(probes(-0.01 * mean, ranks=3))["ok"]
