"""bench.py says where it ran, and fails when something failed.

The script used to probe the backend in a child, fall back to the CPU
when the probe hung and exit 0 with a CPU number under a device
metric's name; a failing section became an ``extra["*_error"]`` note.
These tests run the real script as a subprocess (the way a driver does)
and hold it to the opposite: no TPU and no request for the CPU means a
non-zero exit and no metric line; an asked-for CPU run names
``platform: cpu`` on its line; a failing model fails the run.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")
sys.path.insert(0, REPO)
import bench as bench_mod  # noqa: E402


def _run_bench(tmp_path, env_extra, timeout=600):
    env = dict(os.environ)
    env["HOROVOD_PLATFORM"] = "cpu"
    env.update(env_extra)
    for k, v in list(env.items()):
        if v is None:
            del env[k]
    r = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=timeout, cwd=str(tmp_path), env=env)
    return r, _last_json(r.stdout)


def _last_json(text):
    """Last stdout line that parses to the bench's result dict."""
    for line in reversed(text.strip().splitlines()):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            return obj
    return None


def test_no_tpu_and_cpu_not_asked_exits_nonzero_without_metric_line(
        tmp_path):
    """JAX finds no TPU here.  Unless the CPU was asked for, that is a
    failed run: non-zero exit, a message that says so, and no result
    line for anyone to read a number from."""
    r, doc = _run_bench(tmp_path, {"HOROVOD_PLATFORM": None,
                                   "JAX_PLATFORMS": None,
                                   "BENCH_MODELS": "none"}, timeout=180)
    assert r.returncode != 0
    assert doc is None, r.stdout
    assert "metric" not in r.stdout
    assert "no TPU found" in r.stderr
    assert not (tmp_path / "bench_partial.json").exists()


@pytest.mark.parametrize("var", ["JAX_PLATFORMS", "HOROVOD_PLATFORM"])
def test_asked_for_cpu_run_names_its_platform_on_the_line(tmp_path, var):
    r, doc = _run_bench(tmp_path, {"HOROVOD_PLATFORM": None,
                                   "JAX_PLATFORMS": None, var: "cpu",
                                   "BENCH_MODELS": "none"}, timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    assert doc["platform"] == "cpu" and doc["device_kind"] == "cpu"
    assert doc["device_count"] >= 1
    assert doc["value"] is None and doc["vs_baseline"] is None


def test_a_failing_model_fails_the_run(tmp_path):
    """A failure is not a note in ``extra``: the run exits 1 and the
    line (it still lands, with whatever was measured) carries the
    error.  The old script exited by the ResNet-50 headline alone."""
    r, doc = _run_bench(tmp_path, {
        "BENCH_MODELS": "vgg16", "BENCH_FORCE_FAIL": "vgg16"}, timeout=180)
    assert r.returncode == 1
    assert doc is not None, f"no JSON line: {r.stdout!r}\n{r.stderr[-2000:]}"
    assert "BENCH_FORCE_FAIL" in doc["error"]
    assert doc["platform"] == "cpu"
    assert not any(k.endswith("_error") for k in doc["extra"])
    partial = json.loads((tmp_path / "bench_partial.json").read_text())
    assert partial["metric"] == doc["metric"]


def test_unknown_model_name_fails_the_run(tmp_path):
    """A typo in BENCH_MODELS must not read as "measure nothing, exit
    0"."""
    r, doc = _run_bench(tmp_path, {"BENCH_MODELS": "resnet"}, timeout=180)
    assert r.returncode == 1
    assert "unknown model" in doc["error"]


def test_no_fallback_left_in_the_script():
    src = open(BENCH).read()
    for gone in ("falling back", "_probe_" + "backend", "_run_" + "sections",
                 "BENCH_CHILD", "BENCH_NO_REPROBE", "_PEAK_FLOPS"):
        assert gone not in src, gone


@pytest.mark.slow
def test_resnet_bench_int8_compression_cpu(tmp_path):
    """The quantized (HOROVOD_COMPRESSION=int8) ResNet-50 synthetic
    bench runs end-to-end on an asked-for CPU run: a number lands,
    the extras record the compression mode + block size (a quantized
    img/s is not comparable to a full-precision one without them), and
    the training loss stays finite — the accuracy-regression guard for
    the quantized wire."""
    r, doc = _run_bench(tmp_path, {
        "BENCH_MODELS": "resnet50",
        "BENCH_SKIP_SIDE": "1",
        "HOROVOD_COMPRESSION": "int8",
    })
    assert doc is not None, f"no JSON: {r.stdout!r}\n{r.stderr[-2000:]}"
    assert r.returncode == 0, r.stderr[-2000:]
    assert doc["value"] and doc["value"] > 0
    assert doc["extra"]["compression"] == "int8"
    assert doc["extra"]["quant_block_size"] == 256
    loss = doc["extra"]["resnet50_final_loss"]
    assert np.isfinite(loss) and loss < 20, loss


@pytest.mark.slow
def test_resnet_bench_zero3_cpu(tmp_path):
    """--zero-stage 3 end-to-end on an asked-for CPU run: the train step
    runs on shard-resident params (forward through the prefetched
    gather, shard-shaped updates), a headline number lands, and the
    extras stamp the N-fold memory story (zero_stage + param/grad/
    opt-state bytes per chip)."""
    r, doc = _run_bench(tmp_path, {
        "BENCH_MODELS": "resnet50",
        "BENCH_SKIP_SIDE": "1",
        "HOROVOD_ZERO_STAGE": "3",
    })
    assert doc is not None, f"no JSON: {r.stdout!r}\n{r.stderr[-2000:]}"
    assert r.returncode == 0, r.stderr[-2000:]
    assert doc["value"] and doc["value"] > 0
    assert doc["extra"]["zero_stage"] == 3
    assert doc["extra"]["resnet50_zero_stage_applied"] == 3
    pb = doc["extra"]["resnet50_param_bytes_per_chip"]
    gb = doc["extra"]["resnet50_grad_bytes_per_chip"]
    ob = doc["extra"]["resnet50_opt_state_bytes_per_chip"]
    assert pb > 0 and gb > 0 and ob > 0
    # world size 1 on CPU: shards == full buffers; the relation that
    # must hold everywhere is grads/opt-state tracking the shard size
    assert gb <= pb * 1.01
    loss = doc["extra"]["resnet50_final_loss"]
    assert np.isfinite(loss) and loss < 20, loss


@pytest.mark.slow
def test_transformer_bench_tiny_cpu(tmp_path):
    """The transformer side-metric path runs end-to-end (tiny config on
    CPU) — a deterministic bug here must show up in CI, not only as a
    lost metric on the real run."""
    r, doc = _run_bench(tmp_path, {
        "BENCH_MODELS": "none",
        "BENCH_TRANSFORMER": "1",
        "BENCH_TRANSFORMER_TINY": "1",
    })
    assert doc is not None, f"no JSON: {r.stdout!r}\n{r.stderr[-2000:]}"
    assert doc["extra"].get("transformer_lm_tokens_per_sec", 0) > 0, doc


def test_build_step_steps_per_dispatch_equivalence(hvd_single):
    """k scanned steps in one dispatch (BENCH_STEPS_PER_DISPATCH) must
    walk the same trajectory as k separate dispatches — checked with a
    tiny convnet (ResNet would dominate CI time)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    class TinyConv(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(4, (3, 3))(x)
            x = nn.relu(x)
            x = x.mean(axis=(1, 2))
            return nn.Dense(10)(x)

    hvd = hvd_single
    model = TinyConv()
    imgs = jnp.asarray(np.random.RandomState(0).rand(2, 8, 8, 3),
                       jnp.float32)
    lbls = jnp.asarray([1, 2], jnp.int32)

    def run(spd, calls):
        variables = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)}, imgs, train=True)
        params = variables["params"]
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                       op=hvd.Average, axis_name="hvd")
        opt_state = opt.init(params)
        step = bench_mod._build_step(model, params, None, opt, opt_state,
                                     hvd.world_mesh(),
                                     steps_per_dispatch=spd)
        p, bs, os_, loss = params, None, opt_state, None
        step_no = 0
        for _ in range(calls):
            p, bs, os_, loss = step(p, bs, os_, imgs, lbls,
                                    jnp.int32(step_no))
            step_no += spd
        return float(np.asarray(loss)[0]), p

    loss_a, params_a = run(1, 4)
    loss_b, params_b = run(4, 1)
    assert np.isclose(loss_a, loss_b, rtol=1e-5), (loss_a, loss_b)
    for a, b in zip(jax.tree_util.tree_leaves(params_a),
                    jax.tree_util.tree_leaves(params_b)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_sigterm_still_emits_json(tmp_path):
    """An outer timeout kills with SIGTERM; the handler must flush the
    JSON line (finally blocks don't run on default SIGTERM)."""
    import signal
    import time as _time

    env = dict(os.environ)
    env.update({"HOROVOD_PLATFORM": "cpu", "BENCH_MODELS": "resnet50",
                "BENCH_SIGTERM_TEST_SLEEP": "60"})
    proc = subprocess.Popen([sys.executable, BENCH],
                            stdout=subprocess.PIPE, text=True,
                            cwd=str(tmp_path), env=env)
    _time.sleep(8)  # imports + hvd.init()
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    doc = _last_json(out)
    assert doc is not None, f"no JSON after SIGTERM: {out!r}"
    assert "terminated by signal" in doc.get("error", "")


def test_overlap_flags_export_env(monkeypatch):
    """--overlap / --overlap-chunks export the HOROVOD_* env for every
    spawned rank."""
    args = bench_mod._parse_args(["--overlap", "--overlap-chunks", "6"])
    assert args.overlap is True and args.overlap_chunks == 6
    args = bench_mod._parse_args([])
    assert args.overlap is None and args.overlap_chunks is None


def test_zero_stage_cli(monkeypatch):
    args = bench_mod._parse_args(["--zero-stage", "3",
                                  "--zero-prefetch-chunks", "8"])
    assert args.zero_stage == 3 and args.zero_prefetch_chunks == 8
    args = bench_mod._parse_args([])
    assert args.zero_stage is None and args.zero_prefetch_chunks is None
