"""Family ``lm_moe_mla`` (the ``joyai-llm-flash`` configuration) on the
CPU at a toy size: the system against the plain reference, each term of
the mathematics left out in turn, the hand-worked operation counts, the
configuration file against the published numbers, and the toy cell
through the harness with the new per-layer metrics on its traced line.

Nothing here loads the TPU library.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmark import experts, manifest, reduce, roofline, scopes
from test_benchmark_harness import run_cell, toy_root  # noqa: F401 (fixture)

CELL = "joyai-llm-flash.s8192.epshare"
NEW_METRICS = ("mla_share", "moe_share", "mtp_share", "mla_flash_roofline",
               "moe_experts_roofline", "moe_load_max_over_mean")
PART_METRICS = ("fwd_share", "bwd_share", "optimizer_share",
                "grad_reduce_share", "unscoped_share")
# float32 on both sides on the CPU: the system and the reference differ
# by the order of their sums (read: 1e-7 to 6e-7); a left-out term has
# to fail limits a thousand times that
TIGHT = {"loss_rtol": 1e-4,
         "group_rtol": dict.fromkeys(("mla", "dense", "router", "experts",
                                      "shared", "mtp", "embed_head"), 1e-3)}


def _toy(name: str) -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "toy", name), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family():
    return manifest.load_family(manifest.load_cell(CELL))


def _trainer(family, dtype: str, seed: int = 3000000001):
    """The toy configuration (float32 in its file, so that the toy cell
    passes the chip's limits) computing in ``dtype``."""
    import horovod_tpu as hvd

    config = dict(_toy("configs/toy-moe.json"), compute_dtype=dtype)
    return family.Trainer(config, _toy("traffic/toy-moe-s64.json"), seed, hvd)


# ---------------------------------------------------------------------------
# The system against the reference
# ---------------------------------------------------------------------------


def test_float32_system_is_the_reference_to_rounding(family):
    """Loss and every group's gradient norm — MLA, the dense layer,
    router, routed experts, shared expert, MTP module, embedding and
    head — and the same top-k everywhere."""
    record = _trainer(family, "float32").check_reference()
    assert record["ok"], record
    assert record["loss_rel_err"] < 1e-5
    assert max(record["grad_norm_rel_err"].values()) < 1e-5, record
    assert record["grad_norm"]["bias"] == 0.0
    assert record["pairs_sent_otherwise"] == [0, 0, 0]
    assert all(n > 0 for n in record["pairs_sent"])


def test_bfloat16_stream_stays_near_the_reference_at_toy_size(family):
    """The stream the cell runs (bf16 products and residual stream)
    against the float32 reference.  The chip's limits are for sums over
    8,192 tokens at a hidden size of 2,048; 64 tokens at 32 average the
    rounding of ten thousand times fewer bf16 terms and read 0.03-0.2 %
    in the loss and 0.1-2.7 % in the norms (three seeds), so the limits
    here are 1 % and 8 %: a left-out term moves a group by tens of
    percent.  The selections that differ are counted: a handful of near
    ties, not a tenth of the pairs."""
    trainer = _trainer(family, "bfloat16")
    *readings, sent, wanted = trainer.readings()
    record = family.compare(
        *readings, loss_rtol=1e-2,
        group_rtol=dict.fromkeys(family.GROUP_RTOL, 8e-2))
    assert record["ok"], record
    otherwise = abs(sent - wanted).sum(axis=1)
    assert (otherwise <= 0.1 * sent.sum(axis=1)).all(), (sent, wanted)


def _left_out(family, monkeypatch, term: str):
    """The float32 system with ``term`` left out of it, against the
    whole reference."""
    import jax

    from horovod_tpu.models import blocks
    from horovod_tpu.parallel import moe

    trainer = _trainer(family, "float32")
    if term == "mtp_term":
        trainer.cfg = dataclasses.replace(trainer.cfg, mtp_lambda=0.0)
    elif term == "scale":
        trainer.cfg = dataclasses.replace(trainer.cfg, routed_scale=1.0)
    elif term == "shared_expert":
        whole = moe.moe_layer
        monkeypatch.setattr(
            moe, "moe_layer", lambda x, params, **how: whole(
                x, {k: v for k, v in params.items() if k != "shared"}, **how))
    elif term == "rotary_on_the_shared_key":
        rotary = blocks.rotary
        monkeypatch.setattr(
            blocks, "rotary",
            lambda x, pos, theta: x if x.ndim == 3 else rotary(x, pos, theta))
    elif term == "bias_in_the_weights_too":
        # b belongs in the selection only: here it reaches the weights
        def route(x, router_w, bias, top_k, scale):
            import jax.numpy as jnp

            scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w) + bias
            picked, ids = jax.lax.top_k(scores, top_k)
            return ids, scale * picked / picked.sum(-1, keepdims=True)

        monkeypatch.setattr(moe, "route", route)
    else:
        raise AssertionError(term)
    return trainer


@pytest.fixture
def fresh_traces():
    """``jax.checkpoint`` keeps the traces of the recomputed block: a
    patched function must not meet one made before the patch, nor leave
    its own behind."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("term", ["mtp_term", "shared_expert", "scale",
                                  "rotary_on_the_shared_key",
                                  "bias_in_the_weights_too"])
def test_a_term_left_out_fails_the_comparison(family, monkeypatch,
                                              fresh_traces, term):
    trainer = _left_out(family, monkeypatch, term)
    readings = trainer.readings()[:4]
    record = family.compare(*readings, **TIGHT)
    assert not record["ok"], (term, record)
    # and the cell's own limits: three by tens of percent, the missing
    # rotary by the ``mla`` group (5e-2 here; 5.6e-3 and 7.1e-3 at the
    # cell's size on the chip), ``b`` in the weights by the bias's
    # gradient, which is no longer exactly 0
    assert not family.compare(*readings)["ok"], term


def test_the_whole_system_passes_the_tight_limits(family):
    """The control of the test above."""
    trainer = _trainer(family, "float32")
    assert family.compare(*trainer.readings()[:4], **TIGHT)["ok"]


# ---------------------------------------------------------------------------
# Operations from shapes, by hand
# ---------------------------------------------------------------------------


def test_model_flops_hand_worked():
    """One 8,192-token sequence through the share, multiply-accumulates
    a token forward: latent attention 2048x1536 + 1536x6144 + 2048x576 +
    512x8192 + 4096x2048 = 26,345,472 in each of 6 blocks; layer 0's
    SwiGLU 3x2048x7168 = 44,040,192; an expert layer's router 524,288 +
    (1 shared + 0.5 routed) x 4,718,592 = 7,602,176, in 5 blocks; the
    MTP's joining projection 8,388,608; two heads of 33,095,680.  Sum
    314,703,872.  Attention: 6 blocks x 32 heads x (192 + 128) x
    8192 x 8193 / 2 = 2,061,785,825,280 a sequence.  Times 6 (2 FLOPs,
    3 x forward): 27.84 TFLOP a sequence, 55.68 a step of two."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    per_token = (6 * 26_345_472 + 44_040_192 + 5 * 7_602_176 + 8_388_608
                 + 2 * 33_095_680)
    assert per_token == 314_703_872
    attention = 6 * 32 * 320 * 8192 * 8193 // 2
    want = 6.0 * (8192 * per_token + attention)
    got = family.model_flops_per_sample(cell.config, cell.job)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(27.84e12, rel=1e-3)


def test_kernel_costs_hand_worked():
    """Flash kernels: 64 rows (2 x 32 heads) x 8192 x 8193 x (4 x 192 +
    3 x 128) x 6 blocks = 29.69 TFLOP a step, FLOP-bound (150.7 ms at
    197 TFLOP/s against 14.8 ms of bytes).  Experts: 8,192 expected
    pairs x 3 matrices x 2048 x 768 x 2 FLOPs x 3 passes x 5 layers =
    1.160 TFLOP."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    costs = family.kernel_costs(cell.config, cell.job)
    assert set(costs) == {"mla_flash", "moe_experts"}
    flash = costs["mla_flash"]
    assert flash["flops"] == 6 * 64 * 8192 * 8193 * (4 * 192 + 3 * 128)
    assert flash["bytes"] == 6 * (2 * 64 * 8192 * 1920 + 8 * 64 * 8192)
    assert flash["flops"] / 197e12 > 10 * flash["bytes"] / 819e9
    experts = costs["moe_experts"]
    assert experts["flops"] == 5 * 3 * 2 * 8192 * 3 * 2048 * 768
    assert experts["bytes"] == 5 * 2 * (
        3 * 16 * 3 * 2048 * 768 + 3 * 8192 * (2 * 2048 + 2 * 768))
    # the same for the pairs a routing record shows instead
    assert family.expert_cost(cell.config, 5 * 8192) == experts
    sent = family.expert_cost(cell.config, 30_000)
    assert sent["flops"] == 3 * 2 * 30_000 * 3 * 2048 * 768
    assert sent["bytes"] == 2 * (5 * 3 * 16 * 3 * 2048 * 768
                                 + 3 * 30_000 * (2 * 2048 + 2 * 768))
    # what model_flops_per_sample counts of the same products: the
    # forward's two and twice that, of the kernels' seven
    seq = cell.job["seq"]
    attention = 6.0 * 6 * 32 * 320 * seq * (seq + 1) / 2 * 2
    assert flash["flops"] / attention == pytest.approx(
        (4 * 192 + 3 * 128) / (3 * 320))


# ---------------------------------------------------------------------------
# The configuration file
# ---------------------------------------------------------------------------

PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def test_the_file_states_every_published_width_and_lists_its_cuts():
    """Every key of the model's public ``config.json`` under its own
    name, changed only where ``reduced`` says so: depth, the experts
    held here, the vocabulary slice.  The router keeps its 256 outputs
    and its 8 experts a token."""
    cell = manifest.load_cell(CELL)
    config = cell.config
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "joyai-llm-flash")
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert config["router_width"] == PUBLISHED["n_routed_experts"]
    assert config["n_routed_experts"] >= 8                 # the guide's floors
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    # the arithmetic of the cut, from the program's own parameter tree
    import jax

    from horovod_tpu.models import transformer

    family = manifest.load_family(cell)
    cfg = transformer.TransformerConfig(**family._kwargs(config, cell.job))
    tree = jax.eval_shape(lambda key: transformer.init_params(
        family._DeviceRandn(key), cfg), jax.random.PRNGKey(0))
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(tree))
    assert count == config["parameters"]["total"] == 680_441_088
    assert config["parameters"]["static_bytes"] == 16 * count


# ---------------------------------------------------------------------------
# The toy cell through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def moe_root(toy_root):
    """``test_benchmark_harness.toy_root`` (this module's own copy)
    with a toy configuration of this family, a cell, and the real
    manifest's new per-layer entries for it."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        toy = json.load(f)
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    toy["configs"].append({
        "name": "toy-moe", "source": "none",
        "file": "benchmark/configs/toy-moe.json", "reduced": [],
        "why": "CPU tests"})
    toy["workloads"].append({
        "name": "toy-moe.s64", "config": "toy-moe", "traffic": "toy-moe-s64",
        "chips": 1, "why": "CPU tests: the expert path"})
    for metric in toy["end_to_end"]:
        if metric["name"] == "tokens_per_s_per_chip":
            metric["workloads"].append("toy-moe.s64")
    for name in NEW_METRICS + PART_METRICS:
        entry = dict(real[name])
        if "workloads" in entry:
            entry["workloads"] = ["toy-moe.s64"]
        toy["per_layer"].append(entry)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(toy, f)
    return toy_root


def test_toy_cell_gives_the_new_metrics(moe_root, capfd):
    """The traced line of a run through ``run.run``: correct, every new
    metric but the flash kernels' roofline (the toy sequence is short:
    XLA attention), the five parts adding up to 1 with the blocks
    recomputed in the backward pass, and the load read from the flight
    ring's routing record."""
    code, line, cell = run_cell(moe_root, capfd, "toy-moe.s64", True)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == ({m["name"] for m in cell.per_layer}
                        - {"mla_flash_roofline"})
    assert sum(got[m]["value"] for m in PART_METRICS) \
        == pytest.approx(1.0, abs=1e-6)
    for name in ("mla_share", "moe_share", "mtp_share"):
        assert 0 < got[name]["value"] < 1, name
        assert got[name]["unit"] == "frac_of_busy"
    assert got["moe_experts_roofline"]["value"] > 0
    assert got["moe_experts_roofline"]["unit"] == "%"
    # 4 experts held: the busiest cannot hold more than all the pairs
    assert 1.0 <= got["moe_load_max_over_mean"]["value"] <= 4.0
    with open(os.path.join(cell.out_dir, "records.json")) as f:
        reference = json.load(f)[0]["reference"]
    assert reference["ok"] and len(reference["pairs_sent"]) == 3


def test_readers_give_nothing_where_the_program_has_no_such_name(
        moe_root, monkeypatch):
    """Laid over the parent's checkout — no ``hvd_mla`` / ``hvd_moe`` /
    ``hvd_mtp`` scope, no ``kernel_costs`` of these kernels, no routing
    record — every new reader returns nothing and none raises."""
    from horovod_tpu.runtime import flight

    cell = manifest.load_cell("toy-moe.s64",
                              os.path.join(moe_root, "BENCHMARK.json"))
    trace = reduce.Trace({"chip": [reduce.Op("fusion.1", 0, 10)]}, [], 1)
    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(16))
    flight.record("init", rank=0)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": {"flash_attn": {"flops": 1.0, "bytes": 1.0}}}
    names = {"fusion.1": "jit(step)/jvp(hvd_attn)/dot_general"}
    for found in (None, names):
        monkeypatch.setattr(scopes, "names_of", lambda cell: found)
        for name in NEW_METRICS:
            read = manifest.load_layer_reader(cell, name)
            assert read(trace, counters, cell) is None, name


def test_grouped_product_kernels_count_with_the_expert_layer(
        moe_root, monkeypatch):
    """On the chip the compiler's ``ragged-dot-none.<n>`` kernels carry
    its own ``op_name`` and no scope: ``scopes.scope_ns`` leaves them
    out, ``moe_share`` and ``moe_experts_roofline`` tell them by their
    name.  The roofline's pairs are the routing records', not the
    expectation."""
    from horovod_tpu.runtime import flight

    cell = manifest.load_cell("toy-moe.s64",
                              os.path.join(moe_root, "BENCHMARK.json"))
    family = manifest.load_family(cell)
    ops = [reduce.Op("fusion.1", 0, 10), reduce.Op("ragged-dot-none.2", 10, 30),
           reduce.Op("ragged-dot-metadata", 30, 32),
           reduce.Op("fusion.3", 32, 50)]
    names = {"fusion.1": "jit(step)/jvp(hvd_moe)/hvd_moe_experts/while/body/gather",
             "ragged-dot-none.2": "ragged-dot-none",
             "ragged-dot-metadata": "ragged-dot-metadata",
             "fusion.3": "jit(step)/jvp(hvd_mla)/dot_general"}
    assert scopes.scope_ns(ops, names, "hvd_moe_experts") == 10
    assert experts.scope_ns(ops, names, "hvd_moe_experts") == 32
    trace = reduce.Trace({"chip": ops}, [], 1)
    monkeypatch.setattr(scopes, "names_of", lambda cell: names)
    assert manifest.load_layer_reader(cell, "moe_share")(
        trace, {}, cell) == pytest.approx(32 / 50)
    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(16))
    tokens = cell.job["batch_per_chip"] * cell.job["seq"]
    for layer, pairs in enumerate(([3, 1, 0, 4], [2, 2, 2, 2])):
        flight.record("hvd_moe_route", layer=layer, pairs=pairs,
                      tokens=tokens // 2, top_k=2, dropped=0)
    assert experts.pairs_per_token(experts.routing()) \
        == pytest.approx(16 / (tokens // 2))
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": family.kernel_costs(cell.config, cell.job)}
    got = manifest.load_layer_reader(cell, "moe_experts_roofline")(
        trace, counters, cell)
    assert got == pytest.approx(roofline.percent(
        family.expert_cost(cell.config, 32), counters["peaks"], 32e-9))
