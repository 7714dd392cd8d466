"""Family ``lm_swa_moe`` (the ``trinity-mini`` configuration) on the CPU at
a toy size: the system — sliding-window and full attention by the layer's
type, gated, with QK-norm, rotary positions on the window layers alone,
a norm after every sub-layer, a dense SwiGLU layer and SwiGLU experts —
against the plain reference; a fault planted in each statement of the
configuration in turn; the hand-worked operation counts; the
configuration file against the published numbers; and the toy cell
through the harness with the new per-layer metrics on its traced line.

Nothing here loads the TPU library.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import manifest, reduce, roofline, scopes
from test_benchmark_harness import run_cell, toy_root  # noqa: F401 (fixture)
from test_benchmark_moe_mla import _toy, fresh_traces  # noqa: F401 (fixture)

CELL = "trinity-mini.s16384.epshare"
NEW_METRICS = ("swa_share", "swa_flash_roofline")
SHARED_METRICS = ("gqa_flash_roofline", "moe_share", "moe_experts_roofline",
                  "moe_load_max_over_mean")
PART_METRICS = ("fwd_share", "bwd_share", "optimizer_share",
                "grad_reduce_share", "unscoped_share")
GROUPS = ("attention_window", "attention_full", "dense", "router", "experts",
          "shared", "norms", "embed_head")
# float32 on both sides on the CPU: the system and the reference differ
# by the order of their sums (read: 0 to 2e-7); a planted fault has to
# fail limits a thousand times that
TIGHT = {"loss_rtol": 1e-4, "group_rtol": dict.fromkeys(GROUPS, 1e-3)}


@pytest.fixture(scope="module")
def family():
    return manifest.load_family(manifest.load_cell(CELL))


def _trainer(family, dtype: str, seed: int = 3000000001):
    """The toy configuration (float32 in its file, so that the toy cell
    passes the chip's limits) computing in ``dtype``: layers sliding,
    sliding, full, the first dense — pattern ``SDSEGE`` — a sequence of
    64 under a window of 16."""
    import horovod_tpu as hvd

    config = dict(_toy("configs/toy-swa.json"), compute_dtype=dtype)
    return family.Trainer(config, _toy("traffic/toy-swa-s64.json"), seed, hvd)


# ---------------------------------------------------------------------------
# The system against the reference
# ---------------------------------------------------------------------------


def test_float32_system_is_the_reference_to_rounding(family):
    """Loss and every group's gradient norm — the window layers'
    attention, the full layer's, the dense FFN, router, routed experts,
    shared expert, every norm, embedding and head — and the same top-k
    everywhere; a record an attention sub-layer."""
    trainer = _trainer(family, "float32")
    assert "".join(trainer.cfg.layer_pattern) == "SDSEGE"
    assert trainer.cfg.attn_impl is None and trainer.cfg.post_norm
    record = trainer.check_reference()
    assert record["ok"], record
    assert record["loss_rel_err"] < 1e-5
    assert set(record["grad_norm_rel_err"]) == set(GROUPS)
    assert max(record["grad_norm_rel_err"].values()) < 1e-5, record
    assert record["grad_norm"]["bias"] == 0.0
    assert record["pairs_sent_otherwise"] == [0, 0]
    assert all(n > 0 for n in record["pairs_sent"])
    assert [(r["layer"], r["layer_kind"], r["window"])
            for r in record["attention_windows"]] == [
                (0, "S", 16), (2, "S", 16), (4, "G", 0)]
    assert record["router_settling"] == trainer.settled


def test_bfloat16_stream_stays_near_the_reference_at_toy_size(family):
    """The stream the cell runs (bf16 products and residual stream;
    norms, router, gate and logits in f32) against the float32
    reference.  64 tokens at a hidden size of 32 average the rounding of
    far fewer bf16 terms than the cell's 16,384 at 2,048, so the limits
    here are 1 % and 8 %: a planted fault moves a group by tens of
    percent."""
    trainer = _trainer(family, "bfloat16")
    *readings, sent, wanted = trainer.readings()
    record = family.compare(*readings, loss_rtol=1e-2,
                            group_rtol=dict.fromkeys(GROUPS, 8e-2))
    assert record["ok"], record
    assert (abs(sent - wanted).sum(axis=1) <= 0.15 * sent.sum(axis=1)).all()


FAULTS = ("window_one_key_too_long", "rotary_on_the_full_layer",
          "gate_dropped", "post_norm_dropped", "kv_heads_paired_otherwise",
          "multiplier_dropped")


def plant(monkeypatch, fault: str) -> None:
    """Plant ``fault`` in the program (``models/blocks.py``)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import blocks

    if fault == "window_one_key_too_long":
        # 0 <= i - j <= W for 0 <= i - j < W
        whole = blocks.ring_attention
        monkeypatch.setattr(
            blocks, "ring_attention",
            lambda *a, window=None, **kw: whole(
                *a, window=None if window is None else window + 1, **kw))
    elif fault in ("rotary_on_the_full_layer", "gate_dropped"):
        whole = blocks.gated_gqa

        def faulty(cfg, lp, h, positions, sliding):
            if fault == "gate_dropped":
                # sigmoid(0) is one half everywhere, which the norm
                # after the sub-layer takes out: no gate at all
                return whole(cfg, {**lp, "wg": 0 * lp["wg"]}, h, positions,
                             sliding)
            # positions on every layer, under a window no sequence fills
            return whole(cfg if sliding else dataclasses.replace(
                cfg, window=1 << 30), lp, h, positions, True)

        monkeypatch.setattr(blocks, "gated_gqa", faulty)
    elif fault == "post_norm_dropped":
        whole = blocks.pattern_layer
        monkeypatch.setattr(blocks, "_remat_layer", jax.checkpoint(
            lambda cfg, kind, *rest: whole(
                dataclasses.replace(cfg, post_norm=kind != "D"), kind, *rest),
            static_argnums=(0, 1)))
    elif fault == "kv_heads_paired_otherwise":
        # query head i on key/value head i % 2 instead of i // (4 / 2)
        monkeypatch.setattr(
            blocks, "_over_query_heads",
            lambda t, times: jnp.tile(t, (1, 1, times, 1)))
    elif fault == "multiplier_dropped":
        whole = blocks.pattern_stack
        monkeypatch.setattr(
            blocks, "pattern_stack",
            lambda cfg, *rest: whole(dataclasses.replace(cfg, embed_scale=1.0),
                                     *rest))
    else:
        raise AssertionError(fault)


def _planted(family, monkeypatch, fault: str):
    """The float32 system with ``fault`` planted in it, against the
    whole reference."""
    plant(monkeypatch, fault)
    return _trainer(family, "float32")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(family, monkeypatch,
                                              fresh_traces, fault):
    readings = _planted(family, monkeypatch, fault).readings()[:4]
    record = family.compare(*readings, **TIGHT)
    assert not record["ok"], (fault, record)
    # and the cell's own limits
    assert not family.compare(*readings)["ok"], fault


def test_the_whole_system_passes_the_tight_limits(family, fresh_traces):
    """The control of the test above."""
    trainer = _trainer(family, "float32")
    assert family.compare(*trainer.readings()[:4], **TIGHT)["ok"]


def test_the_reference_in_bfloat16_throughout_fails(family):
    """The nearest precision below the configuration's is not correct by
    the cell's limits even at toy size."""
    readings = _trainer(family, "float32").readings("bfloat16")[:4]
    assert not family.compare(*readings)["ok"]


def test_reference_shares_nothing_with_the_program(family):
    """float32 ``jax.numpy`` at ``highest`` precision: the family's
    reference imports nothing of ``horovod_tpu``, builds its mask from
    positions and takes a query head's key/value head by index."""
    import inspect

    source = inspect.getsource(family)
    start = source.index("# The plain reference")
    end = source.index("# The system under test")
    reference = source[start:end]
    assert "horovod_tpu" not in reference
    assert "ring_attention" not in reference and "pallas" not in reference
    assert 'default_matmul_precision(\n            "highest"' in reference
    assert "apart < window" in reference and "apart >= 0" in reference
    assert "jnp.arange(heads) // (heads // kv)" in reference
    assert "for e in range(config[\"num_experts\"])" in reference


# ---------------------------------------------------------------------------
# Operations from shapes, by hand
# ---------------------------------------------------------------------------


def test_model_flops_hand_worked():
    """One 16,384-token sequence through the share, multiply-accumulates
    a token forward.  An attention sub-layer: q, gate and o 3 x 2048 x
    4096, k and v 2 x 2048 x 512: 27,262,976, in 5 layers.  The dense
    FFN 3 x 2048 x 6144 = 37,748,736.  An expert sub-layer: router
    262,144 + shared expert 6,291,456 + 0.5 routed x 6,291,456:
    9,699,328, in 4 layers.  The head 2048 x 25,024 = 51,249,152.  Sum
    264,110,080.  Attention's products, 32 heads x 256 a pair: a window
    of 2,048 leaves 2048 x 2049 / 2 + 14,336 x 2048 = 31,458,304 pairs
    a head in each of 4 layers, the full layer 16,384 x 16,385 / 2 =
    134,225,920.  Times 6 (2 FLOPs, 3 x forward): 38.75 TFLOP."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    per_token = 5 * 27_262_976 + 37_748_736 + 4 * 9_699_328 + 51_249_152
    assert per_token == 264_110_080
    assert family.window_pairs(16384, 2048) == 31_458_304
    assert family.window_pairs(1024, 2048) == 1024 * 1025 // 2
    products = 32 * 256 * (4 * 31_458_304 + 134_225_920)
    want = 6.0 * (16384 * per_token + products)
    got = family.model_flops_per_sample(cell.config, cell.job)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(38.75e12, rel=1e-3)
    # attention's products are a third of it, the window layers' four
    # together as much as the one full layer
    assert 6.0 * products / got == pytest.approx(0.33, abs=0.005)
    assert 4 * 31_458_304 / 134_225_920 == pytest.approx(0.94, abs=0.01)


def test_kernel_costs_hand_worked():
    """Plain kernels: 32 query heads x 16,384 x 16,385 x 7 x 128 = 7.70
    TFLOP a step in the one full layer.  Windowed kernels: 4 layers x 32
    heads x 31,458,304 pairs x 2 x 7 x 128 = 7.22 TFLOP.  Both
    FLOP-bound: q, o, dO, dq at 32 heads and k, v, dk, dv at 4 move 0.91
    GB a layer.  Experts: 8,192 expected pairs x 3 matrices x 2048 x
    1024 x 2 FLOPs x 3 passes x 4 layers."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    costs = family.kernel_costs(cell.config, cell.job)
    assert set(costs) == {"gqa_flash", "swa_flash", "moe_experts"}
    moved = 2 * 16384 * 128 * (6 * 32 + 6 * 4) + 8 * 32 * 16384
    plain, windowed = costs["gqa_flash"], costs["swa_flash"]
    assert plain["flops"] == 32 * 16384 * 16385 * 7 * 128
    assert plain["bytes"] == moved
    assert windowed["flops"] == 4 * 32 * 31_458_304 * 2 * 7 * 128
    assert windowed["bytes"] == 4 * moved
    for cost in (plain, windowed):
        assert cost["flops"] / 197e12 > 5 * cost["bytes"] / 819e9
    # the window leaves 0.23 of a causal call's pairs
    assert windowed["flops"] / 4 / plain["flops"] == pytest.approx(
        0.2344, abs=1e-3)
    experts = costs["moe_experts"]
    assert experts["flops"] == 4 * 3 * 2 * 8192 * 3 * 2048 * 1024
    assert experts["bytes"] == 2 * (4 * 3 * 8 * 3 * 2048 * 1024
                                    + 3 * 4 * 8192 * (2 * 2048 + 2 * 1024))
    assert family.expert_cost(cell.config, 4 * 8192) == experts


# ---------------------------------------------------------------------------
# The configuration file
# ---------------------------------------------------------------------------


def _published() -> dict:
    """The catalog's ``config`` of the model, where the guides are
    installed; else the file's own statement of what it changed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r["config"] for r in rows if r["name"] == "Trinity-Mini")


def test_the_file_states_every_published_width_and_lists_its_cuts():
    """Every key of the model's public ``config.json`` under its own
    name, changed only where ``reduced`` says so: depth and layer types,
    the dense layers, the experts held here, the vocabulary slice.  The
    router keeps its 128 outputs and its 8 experts a token; the layers
    kept hold one whole period."""
    cell = manifest.load_cell(CELL)
    config = cell.config
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        listed = json.load(f)
    entry = next(c for c in listed["configs"] if c["name"] == "trinity-mini")
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert entry["source"] in config["source"]
    widths = {"hidden_size": 2048, "intermediate_size": 6144,
              "moe_intermediate_size": 1024, "head_dim": 128,
              "num_attention_heads": 32, "num_key_value_heads": 4,
              "num_experts_per_tok": 8, "sliding_window": 2048,
              "route_scale": 2.826, "rms_norm_eps": 1e-5,
              "rope_theta": 10000, "num_shared_experts": 1,
              "max_position_embeddings": 131072}
    for key, value in {**_published(), **widths}.items():
        if key in config["reduced"]:
            continue
        assert config[key] == value, key
    published = config["published"]
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == (
                32, 2, 128, 200192)
    assert published["layer_types"] == 8 * (3 * ["sliding_attention"]
                                            + ["full_attention"])
    for key in config["reduced"]:
        assert config[key] != published[key], key
        if _published():
            assert published[key] == _published()[key], key
    # one dense layer, then one whole period of expert layers
    assert config["layer_types"] == (["sliding_attention"]
                                     + published["layer_types"][4:8])
    assert config["num_hidden_layers"] == 5 and config["num_dense_layers"] == 1
    assert config["router_width"] == published["num_experts"]
    assert config["num_experts"] >= 8                      # the guide's floors
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key], key
    assert config["deployment"]["chips_that_share_a_layer"] == 16
    assert "16,384" in config["deployment"]["why_8_held_and_not_16"]
    for item in ("attention_gate", "qk_norm", "positions", "four_norms",
                 "embedding_multiplier", "rotary_layout", "expert_bias"):
        assert item in config["assumed"], item
    # the cell: one chip, the seventh cell, one of the seven on four
    # chips, five configurations
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload["chips"] == 1 and workload["traffic"] == "s16384.epshare"
    assert (cell.job["seq"], cell.job["batch_per_chip"]) == (16384, 1)
    # (by position, not by a count of the whole list: a later PR adds
    # cells after these and may not edit this file)
    assert listed["workloads"][6] == workload
    assert [w["chips"] for w in listed["workloads"][:7]].count(4) == 1
    assert listed["configs"][4] == entry
    # the arithmetic of the cut, from the program's own parameter tree
    import jax

    from horovod_tpu.models import transformer

    family = manifest.load_family(cell)
    cfg = transformer.TransformerConfig(**family._kwargs(config, cell.job))
    assert "".join(cfg.layer_pattern) == "SDSESESEGE"
    assert (cfg.window, cfg.post_norm, cfg.attn_impl) == (2048, True, None)
    assert cfg.embed_scale == pytest.approx(45.2548, abs=1e-4)
    tree = jax.eval_shape(lambda key: transformer.init_params(
        family._DeviceRandn(key), cfg), jax.random.PRNGKey(0))

    def count(part):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(part))

    stated = config["parameters"]
    two_norms = 2 * 2048                # before and after a sub-layer
    assert count(tree["swa"]) == 4 * (stated["attention"] + two_norms)
    assert count(tree["gattn"]) == stated["attention"] + two_norms \
        == 27_263_232 + 4096
    assert count(tree["dense"]) == stated["dense_ffn"] + two_norms
    assert count(tree["moe"]) == 4 * (
        stated["router_and_bias"] + stated["shared_expert"]
        + 8 * stated["one_expert"] + two_norms)
    assert stated["dense_layer"] == (stated["attention"] + stated["dense_ffn"]
                                     + stated["four_norms"])
    assert count(tree["moe"]["experts"]) == 4 * 8 * stated["one_expert"]
    assert count((tree["embed"], tree["head"], tree["ln_f"])) \
        == stated["embedding_head_final_norm"]
    assert count(tree) == stated["total"] == 504_147_712 \
        == stated["dense_layer"] + 4 * stated["expert_layer"] \
        + stated["embedding_head_final_norm"]
    assert stated["static_bytes"] == 16 * count(tree)


def test_the_hybrid_files_checks_hold_on_the_six_cells_it_knew(monkeypatch):
    """``test_benchmark_hybrid_ssm.py`` asserts a benchmark of exactly
    six cells, which no PR that adds a cell can keep and none may edit
    (tests/conftest.py marks it an expected failure).  Its whole body —
    the hybrid configuration's published widths, cuts and parameter
    arithmetic — run here against the manifest cut to those six."""
    import test_benchmark_hybrid_ssm as hybrid

    whole = json.load

    def first_six(f):
        loaded = whole(f)
        if isinstance(loaded, dict) and "workloads" in loaded:
            loaded["workloads"] = loaded["workloads"][:6]
        return loaded

    monkeypatch.setattr(json, "load", first_six)
    hybrid.test_the_file_states_every_published_width_and_lists_its_cuts()


# ---------------------------------------------------------------------------
# The toy cell through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def swa_root(toy_root):
    """``test_benchmark_harness.toy_root`` (this module's own copy)
    with a toy configuration of this family, a cell, and the real
    manifest's per-layer entries for the real cell."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        toy = json.load(f)
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    toy["configs"].append({
        "name": "toy-swa", "source": "none",
        "file": "benchmark/configs/toy-swa.json", "reduced": [],
        "why": "CPU tests"})
    toy["workloads"].append({
        "name": "toy-swa.s64", "config": "toy-swa",
        "traffic": "toy-swa-s64", "chips": 1,
        "why": "CPU tests: window and full attention in a layer pattern"})
    for metric in toy["end_to_end"]:
        if metric["name"] == "tokens_per_s_per_chip":
            metric["workloads"].append("toy-swa.s64")
    for name in NEW_METRICS + SHARED_METRICS + PART_METRICS:
        entry = dict(real[name])
        if "workloads" in entry:
            assert CELL in entry["workloads"], name
            entry["workloads"] = ["toy-swa.s64"]
        toy["per_layer"].append(entry)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(toy, f)
    return toy_root


def test_toy_cell_gives_the_new_metrics(swa_root, capfd):
    """The traced line of a run through ``run.run``: correct, every
    metric of the cell but the two kernel rooflines (the toy sequence is
    short: XLA attention), the window layers' share, the five parts
    adding up to 1 with every sub-layer recomputed, and the expert
    layer's readers on this family's three-product ``expert_cost``."""
    code, line, cell = run_cell(swa_root, capfd, "toy-swa.s64", True)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == ({m["name"] for m in cell.per_layer}
                        - {"gqa_flash_roofline", "swa_flash_roofline"})
    assert sum(got[m]["value"] for m in PART_METRICS) \
        == pytest.approx(1.0, abs=1e-6)
    assert 0 < got["swa_share"]["value"] < 1
    assert got["swa_share"]["unit"] == "frac_of_busy"
    assert 0 < got["moe_share"]["value"] < 1
    assert got["moe_experts_roofline"]["value"] > 0
    assert 1.0 <= got["moe_load_max_over_mean"]["value"] <= 4.0
    with open(os.path.join(cell.out_dir, "records.json")) as f:
        reference = json.load(f)[0]["reference"]
    assert reference["ok"] and len(reference["pairs_sent"]) == 2
    assert len(reference["attention_windows"]) == 3


def test_readers_give_nothing_where_the_program_has_no_such_name(
        swa_root, monkeypatch):
    """Laid over the parent's checkout — no ``hvd_swa`` scope, no
    ``*_win`` kernel, no ``kernel_costs`` of it — both new readers return
    nothing and neither raises."""
    cell = manifest.load_cell("toy-swa.s64",
                              os.path.join(swa_root, "BENCHMARK.json"))
    trace = reduce.Trace({"chip": [reduce.Op("fusion.1", 0, 10)]}, [], 1)
    peaks = manifest.load_peaks(cell, "TPU v5 lite")
    names = {"fusion.1": "jit(step)/jvp(hvd_attn)/dot_general"}
    costs = ({"flash_attn": {"flops": 1.0, "bytes": 1.0}},
             {"swa_flash": {"flops": 1.0, "bytes": 1.0}})
    for found in (None, names):
        monkeypatch.setattr(scopes, "names_of", lambda cell: found)
        for cost in costs:
            counters = {"peaks": peaks, "kernel_costs": cost}
            for name in NEW_METRICS:
                read = manifest.load_layer_reader(cell, name)
                assert read(trace, counters, cell) is None, name


def test_each_roofline_reads_its_own_kernels(swa_root, monkeypatch):
    """``swa_flash_roofline`` divides the family's ``swa_flash`` cost by
    the time of the ``*_win`` calls alone, ``gqa_flash_roofline`` the
    ``gqa_flash`` cost by the plain calls' alone: whole names are
    matched, so neither reads the other's; ``swa_share`` is the time
    under ``hvd_swa``, not under ``hvd_gattn``."""
    cell = manifest.load_cell("toy-swa.s64",
                              os.path.join(swa_root, "BENCHMARK.json"))
    family = manifest.load_family(cell)

    def kernel(name, start, end):
        return reduce.Op(f"%{name} = bf16[8]{{0}} custom-call(%q), "
                         + reduce.MOSAIC_TARGET, start, end)

    ops = [reduce.Op("fusion.1", 0, 10), reduce.Op("fusion.2", 10, 40),
           kernel("hvd_flash_fwd_win.3", 40, 50),
           kernel("hvd_flash_bwd_dkv_win.4", 50, 55),
           kernel("hvd_flash_fwd.5", 55, 75),
           kernel("hvd_flash_bwd_dq.6", 75, 100)]
    names = {
        "fusion.1": "jit(step)/jvp(hvd_swa)/dot_general",
        "fusion.2": "jit(step)/transpose(jvp(hvd_gattn))/dot_general",
        "hvd_flash_fwd_win.3":
            "jit(step)/jvp(hvd_swa)/hvd_attn/hvd_flash_fwd_win",
        "hvd_flash_bwd_dkv_win.4":
            "jit(step)/transpose(jvp(hvd_swa))/hvd_attn/hvd_flash_bwd_dkv_win",
        "hvd_flash_fwd.5": "jit(step)/jvp(hvd_gattn)/hvd_attn/hvd_flash_fwd",
        "hvd_flash_bwd_dq.6":
            "jit(step)/transpose(jvp(hvd_gattn))/hvd_attn/hvd_flash_bwd_dq"}
    trace = reduce.Trace({"chip": ops}, [], 1)
    monkeypatch.setattr(scopes, "names_of", lambda cell: names)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": family.kernel_costs(cell.config, cell.job)}
    assert manifest.load_layer_reader(cell, "swa_share")(
        trace, counters, cell) == pytest.approx(25 / 100)
    assert manifest.load_layer_reader(cell, "swa_flash_roofline")(
        trace, counters, cell) == pytest.approx(roofline.percent(
            counters["kernel_costs"]["swa_flash"], counters["peaks"], 15e-9))
    assert manifest.load_layer_reader(cell, "gqa_flash_roofline")(
        trace, counters, cell) == pytest.approx(roofline.percent(
            counters["kernel_costs"]["gqa_flash"], counters["peaks"], 45e-9))
