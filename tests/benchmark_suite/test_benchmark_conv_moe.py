"""Family ``lm_conv_moe`` (the ``lfm2-24b-a2b`` configuration) on the CPU
at a toy size: the system — gated short convolutions beside one
grouped-query attention layer with QK-norm and rotary positions, a dense
SwiGLU layer and sigmoid top-k SwiGLU experts with a selection bias and no
shared expert — against the plain reference; a fault planted in each
statement of the configuration in turn; the shares of the experts adding
up to the uncut layer; the hand-worked operation counts; the configuration
file against the published numbers; and the toy cell through the harness
with the new per-layer metrics on its traced line.

Nothing here loads the TPU library.
"""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, reduce, roofline, scopes
from test_benchmark_harness import run_cell, toy_root  # noqa: F401 (fixture)
from test_benchmark_moe_mla import _toy, fresh_traces  # noqa: F401 (fixture)

CELL = "lfm2-24b-a2b.s8192.epshare"
NEW_METRICS = ("conv_share", "conv_roofline")
SHARED_METRICS = ("gqa_flash_roofline", "attn_share", "moe_share",
                  "moe_experts_roofline", "moe_load_max_over_mean",
                  "loss_head_share")
PART_METRICS = ("fwd_share", "bwd_share", "optimizer_share",
                "grad_reduce_share", "unscoped_share")
GROUPS = ("attention", "conv", "dense", "router", "experts", "norms",
          "embed_head")
# float32 on both sides on the CPU: the system and the reference differ
# by the order of their sums (read: 0 to 7e-8); a planted fault has to
# fail limits a thousand times that
TIGHT = {"loss_rtol": 1e-4, "group_rtol": dict.fromkeys(GROUPS, 1e-3)}


@pytest.fixture(scope="module")
def family():
    return manifest.load_family(manifest.load_cell(CELL))


def _trainer(family, dtype: str, seed: int = 3000000001):
    """The toy configuration (float32 in its file, so that the toy cell
    passes the chip's limits) computing in ``dtype``: layers conv, full
    attention, conv, conv, the first dense — pattern ``CDRECECE`` — a
    sequence of 64."""
    import horovod_tpu as hvd

    config = dict(_toy("configs/toy-conv.json"), compute_dtype=dtype)
    return family.Trainer(config, _toy("traffic/toy-conv-s64.json"), seed,
                          hvd)


# ---------------------------------------------------------------------------
# The system against the reference
# ---------------------------------------------------------------------------


def test_float32_system_is_the_reference_to_rounding(family):
    """Loss and every group's gradient norm — the attention layer, the
    convolution layers (taps among them), the dense FFN, router, routed
    experts, every norm, embedding and head — and the same top-k
    everywhere; a record an attention and a convolution sub-layer."""
    trainer = _trainer(family, "float32")
    assert "".join(trainer.cfg.layer_pattern) == "CDRECECE"
    assert trainer.cfg.attn_impl is None and not trainer.cfg.post_norm
    assert (trainer.cfg.shared_experts, trainer.cfg.router,
            trainer.params()["conv"]["conv_w"].shape[1]) == (0, "sigmoid", 3)
    record = trainer.check_reference()
    assert record["ok"], record
    assert record["loss_rel_err"] < 1e-5
    assert set(record["grad_norm_rel_err"]) == set(GROUPS)
    assert max(record["grad_norm_rel_err"].values()) < 1e-5, record
    assert record["grad_norm"]["bias"] == 0.0
    assert min(record["grad_norm"][g] for g in GROUPS) > 0
    assert record["pairs_sent_otherwise"] == [0, 0, 0]
    assert all(n > 0 for n in record["pairs_sent"])
    assert [(r["layer"], r["layer_kind"], r["window"])
            for r in record["attention_windows"]] == [(2, "R", 0)]
    assert [(r["layer"], r["taps"], r["channels"])
            for r in record["conv_mix"]] == [(0, 3, 32), (4, 3, 32),
                                             (6, 3, 32)]
    assert record["router_settling"] == trainer.settled
    assert "shared" not in trainer.params()["moe"]


def test_bfloat16_stream_stays_near_the_reference_at_toy_size(family):
    """The stream the cell runs (bf16 products, residual stream and ``B
    * x``; the taps' sum, ``C * v``, norms, router and logits in f32)
    against the float32 reference.  64 tokens at a hidden size of 32
    average the rounding of far fewer bf16 terms than the cell's 8,192 at
    2,048, so the limits here are 1 % and 8 %: a planted fault moves a
    group by tens of percent."""
    trainer = _trainer(family, "bfloat16")
    *readings, sent, wanted = trainer.readings()
    record = family.compare(*readings, loss_rtol=1e-2,
                            group_rtol=dict.fromkeys(GROUPS, 8e-2))
    assert record["ok"], record
    assert (abs(sent - wanted).sum(axis=1) <= 0.15 * sent.sum(axis=1)).all()


FAULTS = ("taps_reversed", "gates_swapped", "qk_norm_dropped",
          "positions_dropped", "kv_heads_paired_otherwise",
          "bias_in_the_weights_too")


def plant(monkeypatch, fault: str) -> None:
    """Plant ``fault`` in the program (``models/blocks.py``,
    ``parallel/moe.py``)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import blocks
    from horovod_tpu.parallel import moe

    if fault == "taps_reversed":
        # tap k on the row k steps back, not taps - 1 - k
        whole = blocks.causal_conv
        monkeypatch.setattr(blocks, "causal_conv",
                            lambda x, w, bias=None: whole(x, w[::-1], bias))
    elif fault == "gates_swapped":
        # [C | B | x]: C before the convolution, B after it
        whole = blocks.conv_mix

        def swapped(cfg, lp, h):
            w, d = lp["w_in"], cfg.d_model
            return whole(cfg, {**lp, "w_in": jnp.concatenate(
                [w[:, d:2 * d], w[:, :d], w[:, 2 * d:]], axis=1)}, h)

        monkeypatch.setattr(blocks, "conv_mix", swapped)
    elif fault == "qk_norm_dropped":
        # the heads' q and k times their gains, not normalised
        whole, norm = blocks._normed_heads, blocks._rmsnorm

        def unnormed(*args, **kwargs):
            blocks._rmsnorm = lambda x, g, eps=1e-6: (
                x.astype(jnp.float32) * g).astype(x.dtype)
            try:
                return whole(*args, **kwargs)
            finally:
                blocks._rmsnorm = norm

        monkeypatch.setattr(blocks, "_normed_heads", unnormed)
    elif fault == "positions_dropped":
        monkeypatch.setattr(blocks, "rotary", lambda x, *args, **kwargs: x)
    elif fault == "kv_heads_paired_otherwise":
        # query head i on key/value head i % 2 instead of i // (4 / 2)
        monkeypatch.setattr(
            blocks, "_over_query_heads",
            lambda t, times: jnp.tile(t, (1, 1, times, 1)))
    elif fault == "bias_in_the_weights_too":
        # the selection bias added to the scores that weigh the experts
        whole = moe.route

        def faulty(x, router_w, bias, top_k, scale):
            ids, _ = whole(x, router_w, bias, top_k, scale)
            scores = jax.nn.sigmoid(
                x.astype(jnp.float32) @ router_w.astype(jnp.float32)) + bias
            picked = jnp.take_along_axis(scores, ids, axis=-1)
            return ids, scale * picked / picked.sum(-1, keepdims=True)

        monkeypatch.setattr(moe, "route", faulty)
    else:
        raise AssertionError(fault)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(family, monkeypatch,
                                              fresh_traces, fault):
    """Each fails the tight limits, and the cell's own."""
    plant(monkeypatch, fault)
    readings = _trainer(family, "float32").readings()[:4]
    record = family.compare(*readings, **TIGHT)
    assert not record["ok"], (fault, record)
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
    reference imports nothing of ``horovod_tpu``, sums the convolution's
    taps over shifted rows itself and takes a query head's key/value
    head by index."""
    import inspect

    source = inspect.getsource(family)
    start = source.index("# The plain reference")
    end = source.index("# The system under test")
    reference = source[start:end]
    assert "horovod_tpu" not in reference and "causal_conv" not in reference
    assert "ring_attention" not in reference and "pallas" not in reference
    assert 'default_matmul_precision(\n            "highest"' in reference
    assert "for k in range(taps)" in reference
    assert "jnp.arange(heads) // (heads // kv)" in reference
    assert "for e in range(config[\"num_experts\"])" in reference


def test_the_shares_add_up(family):
    """The parts that all 8 shares of 64 experts give — each chip its 8,
    routing over all 64 with the selection bias — summed, are the uncut
    reference's expert layer (the family's, holding all 64): there is no
    shared expert to count once."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.parallel import moe

    rng = np.random.RandomState(9)
    d, f = 16, 8
    w = {"router": jnp.asarray(rng.randn(d, 64) / 4, jnp.float32),
         "bias": jnp.asarray(rng.randn(64) * 0.01, jnp.float32),
         "experts": {name: jnp.asarray(rng.randn(64, *shape) / 4, jnp.float32)
                     for name, shape in (("w_gate", (d, f)), ("w_up", (d, f)),
                                         ("w_down", (f, d)))}}
    x = jnp.asarray(rng.randn(40, d), jnp.float32)
    uncut = {"num_experts": 64, "num_experts_per_tok": 4,
             "routed_scaling_factor": 1}
    with jax.default_matmul_precision("highest"):
        whole, sent = family._experts(uncut, w, x)
        total, pairs = jnp.zeros_like(whole), 0
        for share in range(8):
            held = {**w, "experts": jax.tree_util.tree_map(
                lambda a: a[8 * share:8 * share + 8], w["experts"])}
            out, count = moe.moe_layer(x, held, top_k=4, scale=1.0,
                                       first=8 * share)
            total, pairs = total + out, pairs + int(count.sum())
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert pairs == int(sent.sum()) == 40 * 4


# ---------------------------------------------------------------------------
# Operations from shapes, by hand
# ---------------------------------------------------------------------------


def test_model_flops_hand_worked():
    """One 8,192-token sequence through the share, multiply-accumulates a
    token forward.  A convolution sub-layer: in-projection 2048 x 6144,
    out-projection 2048 x 2048, 3 taps a channel: 16,783,360, in 4
    layers.  The attention sub-layer: q and o 2 x 2048 x 2048, k and v 2
    x 2048 x 512: 10,485,760.  The dense FFN 3 x 2048 x 11,776 =
    72,351,744.  An expert sub-layer: router 131,072 + 0.5 routed x 3 x
    2048 x 1536: 4,849,664, in 4 layers.  The head 2048 x 8192 =
    16,777,216.  Sum 186,146,816.  Attention's products, 32 heads x 128
    a pair over 8,192 x 8,193 / 2 causal pairs.  Times 6 (2 FLOPs, 3 x
    forward): 9.974 TFLOP a sequence, 1.2175 GFLOP a token."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    conv = 4 * 2048 * 2048 + 3 * 2048
    assert conv == 16_783_360
    per_token = (4 * conv + 10_485_760 + 72_351_744 + 4 * 4_849_664
                 + 16_777_216)
    assert per_token == 186_146_816
    products = 32 * 128 * 8192 * 8193 // 2
    want = 6.0 * (8192 * per_token + products)
    got = family.model_flops_per_sample(cell.config, cell.job)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(9.974e12, rel=1e-3)
    assert got / 8192 == pytest.approx(1.2175e9, rel=1e-3)
    # the convolution sub-layers are a third of it, attention's products
    # a twelfth
    assert 6.0 * 8192 * 4 * conv / got == pytest.approx(0.331, abs=0.002)
    assert 6.0 * products / got == pytest.approx(0.0827, abs=0.001)


def test_kernel_costs_hand_worked():
    """Plain kernels at head_dim 64: 32 query heads x 2 sequences x 8,192
    x 8,193 x 7 x 64 = 1.92 TFLOP a step, FLOP-bound (q, o, dO, dq at 32
    heads and k, v, dk, dv at 8 move 0.51 GB).  The convolution
    sub-layers: 16,384 tokens x (2 x (4 passes x 2048 x 6144 + 3 x 2048
    x 2048) + 28 x 2048) a layer, 8.25 TFLOP a step, 41.9 ms at 197
    TFLOP/s, over 15 tensors of 16,384 x 2,048 bf16 a layer for the gates
    and taps, 4.03 GB, 4.92 ms at 819 GB/s: bound by the FLOPs.  Experts: 8,192 expected pairs x 3
    matrices x 2048 x 1536 x 2 FLOPs x 3 passes x 4 layers."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    costs = family.kernel_costs(cell.config, cell.job)
    assert set(costs) == {"gqa_flash", "conv", "moe_experts"}
    plain, conv = costs["gqa_flash"], costs["conv"]
    assert plain["flops"] == 2 * 32 * 8192 * 8193 * 7 * 64
    assert plain["bytes"] == (2 * 2 * 8192 * 64 * (6 * 32 + 6 * 8)
                              + 8 * 2 * 32 * 8192) == 507_510_784
    assert plain["flops"] / 197e12 > 10 * plain["bytes"] / 819e9
    assert conv["bytes"] == 4 * 15 * 16384 * 2048 * 2 == 4_026_531_840
    assert conv["flops"] == 4 * 16384 * (2 * (4 * 2048 * 6144
                                              + 3 * 2048 * 2048)
                                         + 4 * 7 * 2048)
    assert conv["flops"] / 197e12 == pytest.approx(41.88e-3, rel=1e-3)
    assert conv["bytes"] / 819e9 == pytest.approx(4.916e-3, rel=1e-3)
    assert conv["flops"] / 197e12 > 8 * conv["bytes"] / 819e9
    experts = costs["moe_experts"]
    assert experts["flops"] == 4 * 3 * 2 * 8192 * 3 * 2048 * 1536
    assert experts["bytes"] == 2 * (4 * 3 * 8 * 3 * 2048 * 1536
                                    + 3 * 4 * 8192 * (2 * 2048 + 2 * 1536))
    assert family.expert_cost(cell.config, 4 * 8192) == experts


# ---------------------------------------------------------------------------
# The configuration file
# ---------------------------------------------------------------------------


def _published() -> dict:
    """The catalog's ``config`` of the model, where ``HVD_MODEL_CATALOG``
    names a catalog file (one JSON object a line); else the file's own
    statement of what it changed."""
    path = os.environ.get("HVD_MODEL_CATALOG", "")
    if not path or not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r["config"] for r in rows if r["name"] == "LFM2-24B-A2B")


def test_the_file_states_every_published_width_and_lists_its_cuts():
    """Every key of the model's public ``config.json`` under its own
    name, nested groups whole, changed only where ``reduced`` says so:
    depth and layer types, the dense layers, the experts held here, the
    vocabulary slice.  The router keeps its 64 outputs and its 4 experts
    a token; the layers kept hold one whole period."""
    cell = manifest.load_cell(CELL)
    config = cell.config
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        listed = json.load(f)
    entry = next(c for c in listed["configs"] if c["name"] == "lfm2-24b-a2b")
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert entry["source"] in config["source"]
    widths = {"hidden_size": 2048, "intermediate_size": 11776,
              "moe_intermediate_size": 1536, "num_attention_heads": 32,
              "num_key_value_heads": 8, "num_experts_per_tok": 4,
              "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
              "norm_topk_prob": True, "use_expert_bias": True,
              "routed_scaling_factor": 1, "max_position_embeddings": 128000,
              "rope_parameters": {"rope_theta": 1000000,
                                  "rope_type": "default"}}
    for key, value in {**_published(), **widths}.items():
        if key in config["reduced"]:
            continue
        assert config[key] == value, key
    published = config["published"]
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == (
                40, 2, 64, 65536)
    assert published["layer_types"] == (
        ["conv", "conv"] + 9 * ["full_attention", "conv", "conv", "conv"]
        + ["full_attention", "conv"])
    for key in config["reduced"]:
        assert config[key] != published[key], key
        if _published():
            assert published[key] == _published()[key], key
    # published layers 1..5: a dense layer, then one whole period
    assert config["layer_types"] == published["layer_types"][1:6]
    assert config["num_hidden_layers"] == 5 and config["num_dense_layers"] == 1
    assert config["router_width"] == published["num_experts"]
    # the floors of a cut configuration: four layers after the dense one,
    # eight experts, an eighth of the vocabulary
    assert config["num_experts"] == 8
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key], key
    assert config["deployment"]["chips_that_share_a_layer"] == 8
    assert "16,384" in config["deployment"]["why_8_held_and_not_16"]
    for item in ("head_dim", "conv_equations", "qk_norm", "rotary_layout",
                 "router", "untied_head", "expert_bias", "weights"):
        assert item in config["assumed"], item
    # the cell: one chip, the ninth cell, one of the nine on four chips,
    # the seventh configuration (by position, not by a count of the
    # whole list: cells appended after these leave it true)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload["chips"] == 1 and workload["traffic"] == "s8192.epshare"
    assert (cell.job["seq"], cell.job["batch_per_chip"]) == (8192, 2)
    assert listed["workloads"][8] == workload
    assert [w["chips"] for w in listed["workloads"][:9]].count(4) == 1
    assert listed["configs"][6] == entry
    for name in NEW_METRICS:
        metric = next(m for m in listed["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL], name
        assert metric["layer"] == "short_convolution_layer"
        assert metric["moves"] == "tokens_per_s_per_chip"
    for name in SHARED_METRICS:
        metric = next(m for m in listed["per_layer"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL, name
    # the arithmetic of the cut, from the program's own parameter tree
    import jax

    from horovod_tpu.models import transformer

    family = manifest.load_family(cell)
    cfg = transformer.TransformerConfig(**family._kwargs(config, cell.job))
    assert "".join(cfg.layer_pattern) == "CDRECECECE"
    assert (cfg.head_dim, cfg.n_kv_heads, cfg.rope_theta, cfg.attn_impl,
            cfg.shared_experts, cfg.rescale_depth) == (64, 8, 1e6, None, 0, 0)
    tree = jax.eval_shape(lambda key: transformer.init_params(
        family._DeviceRandn(key), cfg), jax.random.PRNGKey(0))
    assert tree["conv"]["conv_w"].shape == (4, config["conv_L_cache"], 2048)

    def count(part):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(part))

    stated = config["parameters"]
    norm = 2048
    assert count(tree["conv"]) == 4 * (stated["conv_mixer"] + norm)
    assert stated["conv_mixer"] == (stated["conv_in_projection"]
                                    + stated["conv_taps"]
                                    + stated["conv_out_projection"])
    assert count(tree["conv"]["conv_w"]) == 4 * stated["conv_taps"]
    assert count(tree["rattn"]) == stated["attention"] + norm
    assert count(tree["dense"]) == stated["dense_ffn"] + norm
    assert stated["experts_held_and_router"] == (8 * stated["one_expert"]
                                                 + stated["router"])
    assert count(tree["moe"]) == 4 * (stated["experts_held_and_router"]
                                      + norm) + stated["selection_bias"]
    assert stated["two_norms"] == 2 * norm
    assert count((tree["embed"], tree["head"])) == stated["embedding_head"]
    assert count(tree["ln_f"]) == stated["final_norm"]
    assert count(tree) - stated["selection_bias"] == stated["total"] \
        == 486_062_208 == (4 * stated["conv_mixer"] + stated["attention"]
                           + stated["dense_ffn"]
                           + 4 * stated["experts_held_and_router"]
                           + 5 * stated["two_norms"]
                           + stated["embedding_head"] + stated["final_norm"])
    # the selection bias is a buffer, and AdamW keeps moments for it too
    assert stated["static_bytes"] == 16 * count(tree)


# ---------------------------------------------------------------------------
# The toy cell through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def conv_root(toy_root):
    """``test_benchmark_harness.toy_root`` (this module's own copy)
    with a toy configuration of this family, a cell, and the real
    manifest's per-layer entries for the real cell."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        toy = json.load(f)
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    toy["configs"].append({
        "name": "toy-conv", "source": "none",
        "file": "benchmark/configs/toy-conv.json", "reduced": [],
        "why": "CPU tests"})
    toy["workloads"].append({
        "name": "toy-conv.s64", "config": "toy-conv",
        "traffic": "toy-conv-s64", "chips": 1,
        "why": "CPU tests: gated short convolutions beside attention"})
    for metric in toy["end_to_end"]:
        if metric["name"] == "tokens_per_s_per_chip":
            metric["workloads"].append("toy-conv.s64")
    for name in NEW_METRICS + SHARED_METRICS + PART_METRICS:
        entry = dict(real[name])
        if "workloads" in entry:
            assert CELL in entry["workloads"], name
            entry["workloads"] = ["toy-conv.s64"]
        toy["per_layer"].append(entry)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(toy, f)
    return toy_root


def test_toy_cell_gives_the_new_metrics(conv_root, capfd):
    """The traced line of a run through ``run.run``: correct, every
    metric of the cell but the kernels' roofline (the toy sequence is
    short: XLA attention), the convolution sub-layers' share and their
    roofline, the five parts adding up to 1 with
    every sub-layer recomputed, and the expert layer's readers."""
    code, line, cell = run_cell(conv_root, capfd, "toy-conv.s64", True)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == ({m["name"] for m in cell.per_layer}
                        - {"gqa_flash_roofline"})
    assert sum(got[m]["value"] for m in PART_METRICS) \
        == pytest.approx(1.0, abs=1e-6)
    assert 0 < got["conv_share"]["value"] < 1
    assert got["conv_share"]["unit"] == "frac_of_busy"
    assert got["conv_roofline"]["value"] > 0
    assert got["conv_roofline"]["unit"] == "%"
    assert 0 < got["loss_head_share"]["value"] < 1
    assert 0 < got["attn_share"]["value"] < 1
    assert 0 < got["moe_share"]["value"] < 1
    assert got["moe_experts_roofline"]["value"] > 0
    assert 1.0 <= got["moe_load_max_over_mean"]["value"] <= 4.0
    with open(os.path.join(cell.out_dir, "records.json")) as f:
        reference = json.load(f)[0]["reference"]
    assert reference["ok"] and len(reference["pairs_sent"]) == 3
    assert len(reference["conv_mix"]) == 3


def test_readers_give_nothing_where_the_program_has_no_such_name(
        conv_root, monkeypatch):
    """Laid over the parent's checkout — no ``hvd_conv`` scope, no
    ``kernel_costs`` of it — the new readers return nothing and none
    raises."""
    cell = manifest.load_cell("toy-conv.s64",
                              os.path.join(conv_root, "BENCHMARK.json"))
    trace = reduce.Trace({"chip": [reduce.Op("fusion.1", 0, 10)]}, [], 1)
    peaks = manifest.load_peaks(cell, "TPU v5 lite")
    names = {"fusion.1": "jit(step)/jvp(hvd_ssm)/dot_general"}
    costs = ({"flash_attn": {"flops": 1.0, "bytes": 1.0}},
             {"conv": {"flops": 1.0, "bytes": 1.0}})
    for found in (None, names):
        monkeypatch.setattr(scopes, "names_of", lambda cell: found)
        for cost in costs:
            counters = {"peaks": peaks, "kernel_costs": cost}
            for name in NEW_METRICS:
                read = manifest.load_layer_reader(cell, name)
                assert read(trace, counters, cell) is None, name


def test_each_reader_reads_its_own_scope(conv_root, monkeypatch):
    """``conv_share`` is the time under ``hvd_conv``, the gates and taps
    under ``hvd_conv_mix`` among it; ``conv_roofline`` divides the
    family's ``conv`` cost by the same time, every pass."""
    cell = manifest.load_cell("toy-conv.s64",
                              os.path.join(conv_root, "BENCHMARK.json"))
    family = manifest.load_family(cell)
    ops = [reduce.Op("fusion.1", 0, 10), reduce.Op("fusion.2", 10, 40),
           reduce.Op("fusion.3", 40, 55), reduce.Op("fusion.4", 55, 75),
           reduce.Op("fusion.5", 75, 100)]
    names = {
        "fusion.1": "jit(step)/jvp(hvd_conv)/dot_general",
        "fusion.2": "jit(step)/jvp(hvd_conv)/hvd_conv_mix/mul",
        "fusion.3": "jit(step)/transpose(jvp(hvd_conv))/hvd_conv_mix/add",
        "fusion.4": "jit(step)/jvp(hvd_rattn)/hvd_attn/dot_general",
        "fusion.5": "jit(step)/transpose(jvp(hvd_moe))/dot_general"}
    trace = reduce.Trace({"chip": ops}, [], 1)
    monkeypatch.setattr(scopes, "names_of", lambda cell: names)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": family.kernel_costs(cell.config, cell.job)}

    def read(name):
        return manifest.load_layer_reader(cell, name)(trace, counters, cell)

    assert read("conv_share") == pytest.approx(55 / 100)
    assert read("conv_roofline") == pytest.approx(roofline.percent(
        counters["kernel_costs"]["conv"], counters["peaks"], 55e-9))
    assert read("attn_share") == pytest.approx(20 / 100)
