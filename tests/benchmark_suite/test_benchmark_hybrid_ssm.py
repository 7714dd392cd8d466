"""Family ``lm_hybrid_ssm`` (the ``nemotron-twotower-30b-a3b``
configuration) on the CPU at a toy size: the system — Mamba-2 mixers on
the chunked scan, grouped-query attention, relu^2 experts — against the
plain reference, which computes the recurrence step by step; a fault
planted in each new kind in turn; the hand-worked operation counts; the
configuration file against the published numbers; and the toy cell
through the harness with the new per-layer metrics on its traced line.

Nothing here loads the TPU library.
"""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, reduce, roofline, scopes
from test_benchmark_harness import run_cell, toy_root  # noqa: F401 (fixture)
from test_benchmark_moe_mla import _toy, fresh_traces  # noqa: F401 (fixture)

CELL = "nemotron-twotower-30b-a3b.s8192.epshare"
NEW_METRICS = ("ssm_share", "ssm_scan_share", "ssm_scan_roofline",
               "gqa_flash_roofline")
EXPERT_METRICS = ("moe_share", "moe_experts_roofline",
                  "moe_load_max_over_mean")
PART_METRICS = ("fwd_share", "bwd_share", "optimizer_share",
                "grad_reduce_share", "unscoped_share")
GROUPS = ("ssm", "attention", "router", "experts", "shared", "embed_head")
# float32 on both sides on the CPU: the system's chunked scan and the
# reference's recurrence differ by the order of their sums (read: 0 to
# 3e-7); a planted fault has to fail limits a thousand times that
TIGHT = {"loss_rtol": 1e-4, "group_rtol": dict.fromkeys(GROUPS, 1e-3)}


@pytest.fixture(scope="module")
def family():
    return manifest.load_family(manifest.load_cell(CELL))


def _trainer(family, dtype: str, seed: int = 3000000001):
    """The toy configuration (float32 in its file, so that the toy cell
    passes the chip's limits) computing in ``dtype``: pattern ``MEM*E``,
    a sequence of 64 in scan chunks of 16."""
    import horovod_tpu as hvd

    config = dict(_toy("configs/toy-hybrid.json"), compute_dtype=dtype)
    return family.Trainer(config, _toy("traffic/toy-hybrid-s64.json"), seed,
                          hvd)


# ---------------------------------------------------------------------------
# The system against the reference
# ---------------------------------------------------------------------------


def test_float32_system_is_the_reference_to_rounding(family):
    """Loss and every group's gradient norm — the state-space layers,
    attention, router, routed experts, shared expert, embedding and
    head — and the same top-k everywhere; a scan record a state-space
    layer."""
    record = _trainer(family, "float32").check_reference()
    assert record["ok"], record
    assert record["loss_rel_err"] < 1e-5
    assert max(record["grad_norm_rel_err"].values()) < 1e-5, record
    assert record["grad_norm"]["bias"] == 0.0
    assert record["pairs_sent_otherwise"] == [0, 0]
    assert all(n > 0 for n in record["pairs_sent"])
    assert [r["layer"] for r in record["ssm_scan"]] == [0, 1]
    for scan in record["ssm_scan"]:
        assert (scan["chunk"], scan["chunks"], scan["heads"],
                scan["state"]) == (16, 4, 4, 16)
        assert scan["least_log_decay"] < 0


def test_settling_brings_the_router_into_balance(family):
    """Before anything is read the trainer runs the balance rule on the
    first batch's routing, forward passes only: the busiest expert comes
    down towards the mean, the selection bias is what moved, and the
    reference, which reads the same bias, still agrees."""
    import horovod_tpu as hvd

    config = dict(_toy("configs/toy-hybrid.json"),
                  router_settling={"rate": 0.01, "rounds": 150})
    job = _toy("traffic/toy-hybrid-s64.json")
    settled = family.Trainer(config, job, 7, hvd)
    drawn = family.Trainer(dict(config, router_settling=None), job, 7, hvd)
    assert drawn.settled is None
    before, after = (settled.settled["busiest_over_mean_before"],
                     settled.settled["busiest_over_mean_after"])
    assert after < 0.8 * before, settled.settled
    for (path, a), b in zip(
            jax_leaves_with_path(settled.params()),
            jax_leaves_with_path(drawn.params())):
        moved = bool((np.asarray(a) != np.asarray(b[1])).any())
        assert moved == (path[-1].key == "bias"), path
    record = settled.check_reference()
    assert record["ok"] and record["router_settling"] == settled.settled


def jax_leaves_with_path(tree):
    import jax

    return jax.tree_util.tree_leaves_with_path(tree)


def test_bfloat16_stream_stays_near_the_reference_at_toy_size(family):
    """The stream the cell runs (bf16 products and residual stream, the
    scan's state and decays in f32) against the float32 reference.  64
    tokens at a hidden size of 32 average the rounding of far fewer bf16
    terms than the cell's 8,192 at 2,688 and read 0.01-0.4 % in the loss
    and 0.01-2.1 % in the norms (three seeds), so the limits here are
    1 % and 8 %: a planted fault moves a group by tens of percent."""
    trainer = _trainer(family, "bfloat16")
    *readings, reports, wanted = trainer.readings()
    record = family.compare(*readings, loss_rtol=1e-2,
                            group_rtol=dict.fromkeys(GROUPS, 8e-2))
    assert record["ok"], record
    sent = reports["pairs"]
    assert (abs(sent - wanted).sum(axis=1) <= 0.1 * sent.sum(axis=1)).all()


def _planted(family, monkeypatch, fault: str):
    """The float32 system with ``fault`` planted in it, against the
    whole reference."""
    import jax.numpy as jnp

    from horovod_tpu.models import blocks
    from horovod_tpu.ops import ssm_scan

    if fault == "d_skip_dropped":
        whole = ssm_scan.ssm_scan
        monkeypatch.setattr(
            ssm_scan, "ssm_scan",
            lambda x, dt, a, b, c, d, chunk: whole(x, dt, a, b, c, 0 * d,
                                                   chunk))
    elif fault == "convolution_not_causal":
        # centred on the step: it reads one step ahead
        def centred(xbc, weight, bias):
            taps, steps = weight.shape[0], xbc.shape[1]
            around = jnp.pad(xbc, ((0, 0), (taps - 2, 1), (0, 0)))
            return bias + sum(around[:, k:k + steps] * weight[k]
                              for k in range(taps))

        monkeypatch.setattr(blocks, "causal_conv", centred)
    elif fault == "kv_heads_paired_otherwise":
        # query head i on key/value head i % 2 instead of i // (32 / 2)
        monkeypatch.setattr(
            blocks, "_over_query_heads",
            lambda t, times: jnp.tile(t, (1, 1, times, 1)))
    else:
        raise AssertionError(fault)
    return _trainer(family, "float32")


@pytest.mark.parametrize("fault", ["d_skip_dropped", "convolution_not_causal",
                                   "kv_heads_paired_otherwise"])
def test_a_planted_fault_fails_the_comparison(family, monkeypatch,
                                              fresh_traces, fault):
    readings = _planted(family, monkeypatch, fault).readings()[:4]
    record = family.compare(*readings, **TIGHT)
    assert not record["ok"], (fault, record)
    # and the cell's own limits, by the group the fault sits in
    assert not family.compare(*readings)["ok"], fault


def test_the_whole_system_passes_the_tight_limits(family):
    """The control of the test above."""
    trainer = _trainer(family, "float32")
    assert family.compare(*trainer.readings()[:4], **TIGHT)["ok"]


def test_the_reference_in_bfloat16_throughout_fails(family):
    """The nearest precision below the configuration's, state and all,
    is not correct by the cell's limits even at toy size."""
    readings = _trainer(family, "float32").readings("bfloat16")[:4]
    assert not family.compare(*readings)["ok"]


def test_reference_shares_nothing_with_the_program(family):
    """float32 ``jax.numpy``: the family's reference imports nothing of
    ``horovod_tpu`` and names neither the chunked scan nor its module."""
    import inspect

    source = inspect.getsource(family)
    start = source.index("# The plain reference")
    end = source.index("# The system under test")
    assert "horovod_tpu" not in source[start:end]
    assert "ssm_scan(" not in source[start:end]
    assert "lax.scan(step" in source[start:end]      # step by step


# ---------------------------------------------------------------------------
# Operations from shapes, by hand
# ---------------------------------------------------------------------------


def test_model_flops_hand_worked():
    """One 8,192-token sequence through the share, multiply-accumulates
    a token forward.  A Mamba-2 layer: in-projection 2688 x 10,304 =
    27,697,152, out-projection 4096 x 2688 = 11,010,048, the recurrence
    2 x 64 x 64 x 128 = 1,048,576: 39,755,776, in 4 layers.  The
    attention layer: q and o 2 x 2688 x 4096, k and v 2 x 2688 x 256:
    23,396,352.  An expert layer: router 344,064 + shared expert 2 x
    2688 x 3712 = 19,955,712 + 0.375 routed x 9,977,856 = 3,741,696:
    24,041,472, in 4 layers.  The head 2688 x 16,384 = 44,040,192.  Sum
    322,625,536.  Attention: 32 heads x 256 x 8192 x 8193 / 2 =
    274,911,461,376 a sequence.  Times 6 (2 FLOPs, 3 x forward): 17.51
    TFLOP a sequence, 35.01 a step of two."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    per_token = (4 * 39_755_776 + 23_396_352 + 4 * 24_041_472 + 44_040_192)
    assert per_token == 322_625_536
    attention = 32 * 256 * 8192 * 8193 // 2
    assert attention == 274_911_461_376
    want = 6.0 * (8192 * per_token + attention)
    got = family.model_flops_per_sample(cell.config, cell.job)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(17.51e12, rel=1e-3)
    # attention's score products are about a tenth of it
    assert 6.0 * attention / got == pytest.approx(0.094, abs=0.002)


def test_kernel_costs_hand_worked():
    """Flash kernels: 64 rows (2 x 32 query heads) x 8192 x 8193 x 7 x
    128 = 3.849 TFLOP a step in the one attention layer, FLOP-bound.
    The recurrence: 16,384 tokens x 2 x 4096 x 128 multiply-accumulates
    x 2 FLOPs x 3 passes x 4 layers = 412.3 GFLOP (2.1 ms at 197
    TFLOP/s) and 16,384 x 54,016 bytes x 4 layers = 3.54 GB (4.3 ms at
    819 GB/s): bound by the bytes.  Experts: 6,144 expected pairs x 2
    matrices x 2688 x 1856 x 2 FLOPs x 3 passes x 4 layers."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    costs = family.kernel_costs(cell.config, cell.job)
    assert set(costs) == {"gqa_flash", "ssm_scan", "moe_experts"}
    flash = costs["gqa_flash"]
    assert flash["flops"] == 64 * 8192 * 8193 * 7 * 128
    assert flash["bytes"] == (2 * 2 * 8192 * 128 * (6 * 32 + 6 * 2)
                              + 8 * 64 * 8192)
    assert flash["flops"] / 197e12 > 10 * flash["bytes"] / 819e9
    scan = costs["ssm_scan"]
    assert scan["flops"] == 4 * 3 * 2 * 16384 * 2 * 4096 * 128
    # a token: x, B, C, y in bf16 and dt in f32 = 20,736 bytes a pass;
    # backward moves them in (dy for y) and the four gradients out
    assert 2 * (2 * 4096 + 2 * 1024) + 4 * 64 == 20_736
    assert scan["bytes"] == 4 * 16384 * (3 * 20_736 - 2 * 4096)
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    experts = costs["moe_experts"]
    assert experts["flops"] == 4 * 3 * 2 * 6144 * 2 * 2688 * 1856
    assert experts["bytes"] == 2 * (4 * 3 * 8 * 2 * 2688 * 1856
                                    + 3 * 4 * 6144 * (2 * 2688 + 1856))
    assert family.expert_cost(cell.config, 4 * 6144) == experts
    # what model_flops_per_sample counts of the attention products: the
    # forward's two and twice that, of the kernels' seven
    seq = cell.job["seq"]
    attention = 6.0 * 32 * 256 * seq * (seq + 1) / 2 * 2
    assert flash["flops"] / attention == pytest.approx(7 / 6)


# ---------------------------------------------------------------------------
# The configuration file
# ---------------------------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}


def test_the_file_states_every_published_width_and_lists_its_cuts():
    """Every key of the model's public ``config.json`` under its own
    name, changed only where ``reduced`` says so: depth and pattern, the
    experts held here, the vocabulary slice.  The router keeps its 128
    outputs and its 6 experts a token; the pattern kept is the published
    one's beginning and holds every kind."""
    cell = manifest.load_cell(CELL)
    config = cell.config
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        listed = json.load(f)
    entry = next(c for c in listed["configs"]
                 if c["name"] == "nemotron-twotower-30b-a3b")
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    kept = config["hybrid_override_pattern"]
    assert PUBLISHED["hybrid_override_pattern"].startswith(kept)
    assert (kept.count("M"), kept.count("E"), kept.count("*")) == (4, 4, 1)
    assert len(kept) == config["num_hidden_layers"] == 9
    assert config["router_width"] == PUBLISHED["n_routed_experts"]
    assert config["n_routed_experts"] >= 8                 # the guide's floors
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key], key
    assert config["deployment"]["chips_that_share_a_layer"] == 16
    # the cell: one chip, the sixth cell, one of the six on four chips
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload["chips"] == 1 and workload["traffic"] == "s8192.epshare"
    assert [w["chips"] for w in listed["workloads"]].count(4) == 1
    assert len(listed["workloads"]) == 6
    # the arithmetic of the cut, from the program's own parameter tree
    import jax

    from horovod_tpu.models import blocks, transformer

    family = manifest.load_family(cell)
    cfg = transformer.TransformerConfig(**family._kwargs(config, cell.job))
    tree = jax.eval_shape(lambda key: transformer.init_params(
        family._DeviceRandom(key), cfg), jax.random.PRNGKey(0))

    def count(part):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(part))

    stated = config["parameters"]
    assert count(tree["ssm"]) == 4 * stated["mamba2_layer"]
    assert count(tree["attn"]) == stated["attention_layer"] == 23_399_040
    assert count(tree["moe"]) == 4 * stated["expert_layer"]
    assert count(tree["moe"]["experts"]) == 4 * 8 * stated["one_expert"]
    assert count(tree) == stated["total"] == 666_963_456
    assert stated["static_bytes"] == 16 * count(tree)
    # the state-space layers' own parameters are drawn as the file says
    assert (blocks.DT_MIN, blocks.DT_MAX, blocks.DT_FLOOR) == (
        config["time_step_min"], config["time_step_max"],
        config["time_step_floor"])


# ---------------------------------------------------------------------------
# The toy cell through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hybrid_root(toy_root):
    """``test_benchmark_harness.toy_root`` (this module's own copy)
    with a toy configuration of this family, a cell, and the real
    manifest's per-layer entries for the real cell."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        toy = json.load(f)
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    toy["configs"].append({
        "name": "toy-hybrid", "source": "none",
        "file": "benchmark/configs/toy-hybrid.json", "reduced": [],
        "why": "CPU tests"})
    toy["workloads"].append({
        "name": "toy-hybrid.s64", "config": "toy-hybrid",
        "traffic": "toy-hybrid-s64", "chips": 1,
        "why": "CPU tests: the layer pattern"})
    for metric in toy["end_to_end"]:
        if metric["name"] == "tokens_per_s_per_chip":
            metric["workloads"].append("toy-hybrid.s64")
    for name in NEW_METRICS + EXPERT_METRICS + PART_METRICS:
        entry = dict(real[name])
        if "workloads" in entry:
            assert CELL in entry["workloads"], name
            entry["workloads"] = ["toy-hybrid.s64"]
        toy["per_layer"].append(entry)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(toy, f)
    return toy_root


def test_toy_cell_gives_the_new_metrics(hybrid_root, capfd):
    """The traced line of a run through ``run.run``: correct, every new
    metric but the flash kernels' roofline (the toy sequence is short:
    XLA attention), the scan's share inside the mixer's, the five parts
    adding up to 1 with every layer recomputed, and the expert layer's
    readers on a two-matrix layer."""
    code, line, cell = run_cell(hybrid_root, capfd, "toy-hybrid.s64", True)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == ({m["name"] for m in cell.per_layer}
                        - {"gqa_flash_roofline"})
    assert sum(got[m]["value"] for m in PART_METRICS) \
        == pytest.approx(1.0, abs=1e-6)
    assert 0 < got["ssm_scan_share"]["value"] < got["ssm_share"]["value"] < 1
    assert got["ssm_share"]["unit"] == "frac_of_busy"
    assert got["ssm_scan_roofline"]["value"] > 0
    assert got["ssm_scan_roofline"]["unit"] == "%"
    assert 0 < got["moe_share"]["value"] < 1
    assert got["moe_experts_roofline"]["value"] > 0
    assert 1.0 <= got["moe_load_max_over_mean"]["value"] <= 4.0
    with open(os.path.join(cell.out_dir, "records.json")) as f:
        reference = json.load(f)[0]["reference"]
    assert reference["ok"] and len(reference["pairs_sent"]) == 2
    assert len(reference["ssm_scan"]) == 2


def test_readers_give_nothing_where_the_program_has_no_such_name(
        hybrid_root, monkeypatch):
    """Laid over the parent's checkout — no ``hvd_ssm`` scope, no
    ``kernel_costs`` of these kernels — every new reader returns nothing
    and none raises."""
    cell = manifest.load_cell("toy-hybrid.s64",
                              os.path.join(hybrid_root, "BENCHMARK.json"))
    trace = reduce.Trace({"chip": [reduce.Op("fusion.1", 0, 10)]}, [], 1)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": {"flash_attn": {"flops": 1.0, "bytes": 1.0}}}
    names = {"fusion.1": "jit(step)/jvp(hvd_attn)/dot_general"}
    for found in (None, names):
        monkeypatch.setattr(scopes, "names_of", lambda cell: found)
        for name in NEW_METRICS:
            read = manifest.load_layer_reader(cell, name)
            assert read(trace, counters, cell) is None, name


def test_scan_roofline_is_the_required_work_over_the_scopes_time(
        hybrid_root, monkeypatch):
    """``ssm_scan_roofline`` divides the family's ``ssm_scan`` cost by
    the time under ``hvd_ssm_scan`` alone (not the mixer's projections
    under ``hvd_ssm``), ``gqa_flash_roofline`` the ``gqa_flash`` cost by
    the three kernels' time."""
    cell = manifest.load_cell("toy-hybrid.s64",
                              os.path.join(hybrid_root, "BENCHMARK.json"))
    family = manifest.load_family(cell)
    kernel = reduce.Op('%hvd_flash_fwd.3 = bf16[8]{0} custom-call(%q), '
                       + reduce.MOSAIC_TARGET, 50, 70)
    ops = [reduce.Op("fusion.1", 0, 10), reduce.Op("fusion.2", 10, 40),
           kernel]
    names = {"fusion.1": "jit(step)/jvp(hvd_ssm)/dot_general",
             "fusion.2": "jit(step)/transpose(jvp(hvd_ssm))/hvd_ssm_scan/mul",
             "hvd_flash_fwd.3": "jit(step)/jvp(hvd_attn)/hvd_flash_fwd"}
    trace = reduce.Trace({"chip": ops}, [], 1)
    monkeypatch.setattr(scopes, "names_of", lambda cell: names)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": family.kernel_costs(cell.config, cell.job)}
    assert manifest.load_layer_reader(cell, "ssm_share")(
        trace, counters, cell) == pytest.approx(40 / 60)
    assert manifest.load_layer_reader(cell, "ssm_scan_share")(
        trace, counters, cell) == pytest.approx(30 / 60)
    assert manifest.load_layer_reader(cell, "ssm_scan_roofline")(
        trace, counters, cell) == pytest.approx(roofline.percent(
            counters["kernel_costs"]["ssm_scan"], counters["peaks"], 30e-9))
    assert manifest.load_layer_reader(cell, "gqa_flash_roofline")(
        trace, counters, cell) == pytest.approx(roofline.percent(
            counters["kernel_costs"]["gqa_flash"], counters["peaks"], 20e-9))
