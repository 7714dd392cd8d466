"""Family ``lm_dsa_moe`` (the ``keye-vl-2.0-30b-a3b`` configuration) on the
CPU at a toy size: the system — an indexer's exact top-k selection of
each query's keys, grouped-query attention over it with QK-norm and
rotary positions in sections, a softmax router without a shared expert —
against the plain reference; a fault planted in each statement of the
configuration in turn; the hand-worked operation counts; the
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

CELL = "keye-vl-2.0-30b-a3b.s16384.epshare"
NEW_METRICS = ("dsa_share", "dsa_index_share", "dsa_attend_roofline",
               "dsa_index_roofline", "dsa_kept_share")
SHARED_METRICS = ("moe_share", "moe_experts_roofline",
                  "moe_load_max_over_mean")
PART_METRICS = ("fwd_share", "bwd_share", "optimizer_share",
                "grad_reduce_share", "unscoped_share")
GROUPS = ("attention", "router", "experts", "norms", "embed_head")
# float32 on both sides on the CPU: the system and the reference differ
# by the order of their sums (read: 0 to 1e-7); a planted fault has to
# fail limits a thousand times that
TIGHT = {"loss_rtol": 1e-4, "group_rtol": dict.fromkeys(GROUPS, 1e-3)}


@pytest.fixture(scope="module")
def family():
    return manifest.load_family(manifest.load_cell(CELL))


def _trainer(family, dtype: str, seed: int = 3000000001):
    """The toy configuration (float32 in its file, so that the toy cell
    passes the chip's limits) computing in ``dtype``: two layers —
    pattern ``IEIE`` — a sequence of 64 of which a query keeps 16."""
    import horovod_tpu as hvd

    config = dict(_toy("configs/toy-dsa.json"), compute_dtype=dtype)
    return family.Trainer(config, _toy("traffic/toy-dsa-s64.json"), seed, hvd)


def _checks(family, trainer, **limits) -> dict:
    """Checks (a), (b) and (c) on what ``trainer`` makes."""
    *readings, reports, wanted = trainer.readings()
    record = family.compare(*readings, **limits)
    record["selection"] = family.selection_checks(
        reports["selections"], wanted["own_pairs"], wanted["common_pairs"],
        trainer.cfg.index_topk, common_share=1.0)
    return record


# ---------------------------------------------------------------------------
# The system against the reference
# ---------------------------------------------------------------------------


def test_float32_system_is_the_reference_to_rounding(family):
    """Loss and every group's gradient norm, the same selection of keys
    in both layers (every row's count ``min(t + 1, 16)``, every kept
    pair the reference's too), the same top-k of experts; the indexer's
    gradient exactly 0 on both sides; a record a selection."""
    trainer = _trainer(family, "float32")
    assert "".join(trainer.cfg.layer_pattern) == "IEIE"
    assert trainer.cfg.attn_impl is None and trainer.cfg.router == "softmax"
    assert trainer.cfg.rope_sections == (1, 1, 2)
    record = trainer.check_reference()
    assert record["ok"], record
    assert record["loss_rel_err"] < 1e-5
    assert set(record["grad_norm_rel_err"]) == set(GROUPS)
    assert max(record["grad_norm_rel_err"].values()) < 1e-5, record
    assert record["grad_norm"]["indexer"] == 0.0
    assert record["reference_grad_norm"]["indexer"] == 0.0
    assert record["grad_norm"]["attention"] > 0
    selection = record["selection"]
    assert selection["rows_with_another_count"] == 0
    # 16 x 17 / 2 + 48 x 16 pairs a sequence
    assert selection["kept_pairs"] == [904, 904]
    assert selection["common_share"] == [1.0, 1.0]
    assert record["pairs_sent_otherwise"] == [0, 0]
    assert all(n > 0 for n in record["pairs_sent"])
    assert [(r["layer"], r["seq"], r["topk"], r["kept_pairs"],
             r["causal_pairs"], r["operand"])
            for r in record["selections"]] == [
                (0, 64, 16, 904, 2080, "packed_mask"),
                (1, 64, 16, 904, 2080, "packed_mask")]
    assert "bias" not in trainer.params()["moe"]
    assert "shared" not in trainer.params()["moe"]


def test_bfloat16_stream_stays_near_the_reference_at_toy_size(family):
    """The stream the cell runs (bf16 products and residual stream;
    norms, the indexer's scores, router and logits in f32) against the
    float32 reference under the system's selection.  64 tokens at a
    hidden size of 32 average the rounding of far fewer bf16 terms than
    the cell's 16,384 at 2,048, so the limits here are 1 % and 8 %: a
    planted fault moves a group by tens of percent.  The two selections
    differ in a few keys at a row's threshold."""
    trainer = _trainer(family, "bfloat16")
    *readings, reports, wanted = trainer.readings()
    record = family.compare(*readings, loss_rtol=1e-2,
                            group_rtol=dict.fromkeys(GROUPS, 8e-2))
    assert record["ok"], record
    selection = family.selection_checks(
        reports["selections"], wanted["own_pairs"], wanted["common_pairs"],
        trainer.cfg.index_topk)
    assert selection["ok"], selection
    assert all(0.97 < share <= 1.0 for share in selection["common_share"])


FAULTS = ("selection_one_key_short", "approximate_top_k", "head_weights_zeroed",
          "qk_norm_dropped",
          "kv_heads_paired_otherwise", "positions_dropped",
          "sigmoid_for_softmax", "weights_not_renormalised")


def plant(monkeypatch, fault: str) -> None:
    """Plant ``fault`` in the program (``models/blocks.py``,
    ``parallel/moe.py``)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import blocks
    from horovod_tpu.parallel import moe

    if fault == "selection_one_key_short":
        whole = blocks.select_keys
        monkeypatch.setattr(
            blocks, "select_keys", lambda cfg, lp, h: whole(
                dataclasses.replace(cfg, index_topk=cfg.index_topk - 1),
                lp, h))
    elif fault == "approximate_top_k":
        # the threshold from every other key: about the right count, not
        # the right keys
        whole = blocks._kth_largest
        monkeypatch.setattr(
            blocks, "_kth_largest",
            lambda keys, k: whole(keys[..., ::2], k // 2))
    elif fault == "head_weights_zeroed":
        # every score 0: each row ties throughout and keeps its first keys
        whole = blocks.select_keys
        monkeypatch.setattr(
            blocks, "select_keys", lambda cfg, lp, h: whole(
                cfg, {**lp, "ww_idx": 0 * lp["ww_idx"]}, h))
    elif fault == "qk_norm_dropped":
        whole = blocks.indexed_gqa
        monkeypatch.setattr(
            blocks, "indexed_gqa", lambda cfg, lp, h, positions: whole(
                dataclasses.replace(cfg, norm_eps=1e6), lp, h, positions))
    elif fault == "kv_heads_paired_otherwise":
        # query head i on key/value head i % 2 instead of i // (4 / 2)
        monkeypatch.setattr(
            blocks, "_over_query_heads",
            lambda t, times: jnp.tile(t, (1, 1, times, 1)))
    elif fault == "positions_dropped":
        monkeypatch.setattr(blocks, "rotary",
                            lambda x, *args, **kwargs: x)
    elif fault == "sigmoid_for_softmax":
        monkeypatch.setattr(moe.jax.nn, "softmax",
                            lambda x, axis=-1: jax.nn.sigmoid(x))
    elif fault == "weights_not_renormalised":
        whole = moe.route

        def faulty(x, router_w, bias, top_k, scale):
            ids, weights = whole(x, router_w, bias, top_k, scale)
            scores = jax.nn.softmax(x.astype(jnp.float32)
                                    @ router_w.astype(jnp.float32), axis=-1)
            return ids, jnp.take_along_axis(scores, ids, axis=-1)

        monkeypatch.setattr(moe, "route", faulty)
    else:
        raise AssertionError(fault)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(family, monkeypatch,
                                              fresh_traces, fault):
    """A selection one key short or approximate fails check (a) or (b);
    a fault in attention, positions or routing fails (c), also by the
    cell's own limits."""
    plant(monkeypatch, fault)
    trainer = _trainer(family, "float32")
    record = _checks(family, trainer, **TIGHT)
    assert not (record["ok"] and record["selection"]["ok"]), (fault, record)
    if fault in ("selection_one_key_short", "approximate_top_k",
                 "head_weights_zeroed"):
        assert not record["selection"]["ok"], (fault, record["selection"])
    if fault == "selection_one_key_short":
        assert record["selection"]["rows_with_another_count"] == 2 * 49
    if fault not in ("selection_one_key_short", "approximate_top_k",
                     "head_weights_zeroed"):
        # and the cell's own limits
        assert not family.compare(*trainer.readings()[:4])["ok"], fault


def test_the_whole_system_passes_the_tight_limits(family, fresh_traces):
    """The control of the test above."""
    record = _checks(family, _trainer(family, "float32"), **TIGHT)
    assert record["ok"] and record["selection"]["ok"], record


def test_the_reference_under_its_own_selection_is_the_same(family):
    """Given no selection the reference attends under its own; in
    float32 that is the system's, key for key, so the loss is the
    same."""
    import jax

    trainer = _trainer(family, "float32")
    tokens, targets = trainer.reference_batch()
    loss, reports, _ = trainer.gradient_program()(trainer.params(), tokens,
                                                  targets)
    own, report = jax.jit(
        lambda p: family.reference_loss(trainer.config, p, tokens, targets))(
            trainer.params())
    under, _ = jax.jit(
        lambda p, s: family.reference_loss(trainer.config, p, tokens,
                                           targets, selections=s))(
            trainer.params(), reports["selections"])
    assert float(own) == pytest.approx(float(loss), rel=1e-6)
    assert float(under) == pytest.approx(float(own), rel=1e-6)
    assert report["own_pairs"].tolist() == report["common_pairs"].tolist() \
        == [904, 904]


def test_reference_shares_nothing_with_the_program(family):
    """float32 ``jax.numpy`` at ``highest`` precision: the family's
    reference imports nothing of ``horovod_tpu``, takes its threshold
    from a sorted top-k, builds its masks itself, unpacks the system's
    selection by its own arithmetic and takes a query head's key/value
    head by index."""
    import inspect

    source = inspect.getsource(family)
    start = source.index("# The plain reference")
    end = source.index("# The system under test")
    reference = source[start:end]
    assert "horovod_tpu" not in reference
    assert "ring_attention" not in reference and "pallas" not in reference
    assert "unpack_keep" not in reference and "pack_keep" not in reference
    assert 'default_matmul_precision(\n            "highest"' in reference
    assert "jax.lax.top_k(scores, topk)" in reference
    assert "jnp.arange(heads) // (heads // kv)" in reference
    assert "for e in range(config[\"num_experts\"])" in reference
    assert "jax.nn.softmax(x @ w[\"router\"], axis=-1)" in reference


# ---------------------------------------------------------------------------
# Operations from shapes, by hand
# ---------------------------------------------------------------------------


def test_model_flops_hand_worked():
    """One 16,384-token sequence through the share, multiply-accumulates
    a token forward.  An attention sub-layer: q and o 2 x 2048 x 4096, k
    and v 2 x 2048 x 512: 18,874,368.  An expert sub-layer: router
    262,144 + 0.5 routed x 3 x 2048 x 768: 2,621,440.  Six layers of
    both: 128,974,848; the head 2048 x 18,992 = 38,895,616: 167,870,464
    a token.  Attention's products, 32 heads x 256 a pair, over the
    2048 x 2049 / 2 + 14,336 x 2048 = 31,458,304 pairs a head that the
    selection leaves, in 6 layers.  Times 6 (2 FLOPs, 3 x forward).
    The indexer, forward only, times 2: projections 2048 x (16 x 64 + 64
    + 16) = 2,260,992 a token, scores 16 x 64 over the 16,384 x 16,385 /
    2 = 134,225,920 causal pairs, in 6 layers.  27.87 TFLOP."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    per_token = 6 * (18_874_368 + 2_621_440) + 38_895_616
    assert per_token == 167_870_464
    assert family.kept_pairs(16384, 2048) == 31_458_304
    assert family.kept_pairs(1024, 2048) == 1024 * 1025 // 2
    assert family.causal_pairs(16384) == 134_225_920
    products = 6 * 32 * 256 * 31_458_304
    indexer = 6 * (16384 * 2_260_992 + 1024 * 134_225_920)
    want = 6.0 * (16384 * per_token + products) + 2.0 * indexer
    got = family.model_flops_per_sample(cell.config, cell.job)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(27.87e12, rel=1e-3)
    # attention over the selection is a third of it, the indexer a
    # thirteenth; a masked causal call computes 4.27 times those pairs
    assert 6.0 * products / got == pytest.approx(0.333, abs=0.005)
    assert 2.0 * indexer / got == pytest.approx(0.075, abs=0.003)
    assert 134_225_920 / 31_458_304 == pytest.approx(4.27, abs=0.01)


def test_kernel_costs_hand_worked():
    """Attention under the selection: 6 layers x 32 heads x 31,458,304
    pairs x 2 x 7 x 128 = 10.82 TFLOP a step, FLOP-bound (q, o, dO, dq
    at 32 heads and k, v, dk, dv at 4 move 0.91 GB a layer).  The
    indexer's scores: 6 x 2 x 16 x 64 x 134,225,920 = 1.65 TFLOP, once.
    Experts: 8,192 expected pairs x 3 matrices x 2048 x 768 x 2 FLOPs x
    3 passes x 6 layers."""
    cell = manifest.load_cell(CELL)
    family = manifest.load_family(cell)
    costs = family.kernel_costs(cell.config, cell.job)
    assert set(costs) == {"dsa_attend", "dsa_index", "moe_experts"}
    moved = 2 * 16384 * 128 * (6 * 32 + 6 * 4) + 8 * 32 * 16384
    attend, index = costs["dsa_attend"], costs["dsa_index"]
    assert attend["flops"] == 6 * 32 * 31_458_304 * 2 * 7 * 128
    assert attend["bytes"] == 6 * moved
    assert index["flops"] == 6 * 2 * 16 * 64 * 134_225_920
    assert index["bytes"] == 6 * (16384 * (2 * 64 * 17 + 4 * 16)
                                  + 16384 * 16384 // 8)
    for cost in (attend, index):
        assert cost["flops"] / 197e12 > 5 * cost["bytes"] / 819e9
    # the selection leaves 0.23 of a causal call's pairs: what a masked
    # causal call can read of this roofline at most
    assert 31_458_304 / 134_225_920 == pytest.approx(0.2344, abs=1e-4)
    experts = costs["moe_experts"]
    assert experts["flops"] == 6 * 3 * 2 * 8192 * 3 * 2048 * 768
    assert experts["bytes"] == 2 * (6 * 3 * 8 * 3 * 2048 * 768
                                    + 3 * 6 * 8192 * (2 * 2048 + 2 * 768))
    assert family.expert_cost(cell.config, 6 * 8192) == experts


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
    return next(r["config"] for r in rows
                if r["name"] == "Keye-VL-2.0-30B-A3B")


def test_the_file_states_every_published_width_and_lists_its_cuts():
    """Every key of the model's public ``config.json`` under its own
    name, nested groups whole, changed only where ``reduced`` says so:
    depth, the experts held here, the vocabulary slice.  The router
    keeps its 128 outputs and its 8 experts a token."""
    cell = manifest.load_cell(CELL)
    config = cell.config
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        listed = json.load(f)
    entry = next(c for c in listed["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] in config["source"]
    widths = {"hidden_size": 2048, "intermediate_size": 6144,
              "moe_intermediate_size": 768, "head_dim": 128,
              "num_attention_heads": 32, "num_key_value_heads": 4,
              "num_experts_per_tok": 8, "num_local_experts": 128,
              "rms_norm_eps": 1e-6, "rope_theta": 10000000,
              "norm_topk_prob": True, "max_position_embeddings": 262144,
              "rope_scaling": {"mrope_section": [16, 24, 24],
                               "rope_type": "default", "type": "default"},
              "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                            "q_chunk_size": 512, "topk": 2048}}
    for key, value in {**_published(), **widths}.items():
        if key in config["reduced"]:
            continue
        assert config[key] == value, key
    published = config["published"]
    assert published == {"num_hidden_layers": 48, "num_experts": 128,
                         "vocab_size": 151936}
    for key in config["reduced"]:
        assert config[key] != published[key], key
        if _published():
            assert published[key] == _published()[key], key
    assert config["router_width"] == published["num_experts"]
    # the guide's floors: four layers or more, eight experts, an eighth
    # of the vocabulary
    assert config["num_hidden_layers"] == 6
    assert config["num_experts"] == 8
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key], key
    assert config["deployment"]["chips_that_share_a_layer"] == 16
    assert "16,384" in config["deployment"]["why_8_held_and_not_16"]
    for item in ("indexer", "indexer_positions_and_norm", "indexer_chunks",
                 "qk_norm", "rotary", "router", "no_gradient_to_the_indexer"):
        assert item in config["assumed"], item
    assert any("alignment loss" in item for item in config["departures"])
    assert any("vision tower" in item for item in config["departures"])
    # the cell: one chip, the eighth cell, one of the eight on four
    # chips, the sixth configuration (by position, not by a count of the
    # whole list: a later PR adds cells after these and may not edit
    # this file)
    workload = next(w for w in listed["workloads"] if w["name"] == CELL)
    assert workload["chips"] == 1 and workload["traffic"] == "s16384.epshare"
    assert (cell.job["seq"], cell.job["batch_per_chip"]) == (16384, 1)
    assert listed["workloads"][7] == workload
    assert [w["chips"] for w in listed["workloads"][:8]].count(4) == 1
    assert listed["configs"][5] == entry
    for name in NEW_METRICS:
        metric = next(m for m in listed["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL], name
        assert metric["layer"] == "attention_kernels"
        assert metric["moves"] == "tokens_per_s_per_chip"
    for name in SHARED_METRICS:
        metric = next(m for m in listed["per_layer"] if m["name"] == name)
        assert CELL in metric["workloads"], name
    # the arithmetic of the cut, from the program's own parameter tree
    import jax

    from horovod_tpu.models import transformer

    family = manifest.load_family(cell)
    cfg = transformer.TransformerConfig(**family._kwargs(config, cell.job))
    assert "".join(cfg.layer_pattern) == "IE" * 6
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) \
        == (16, 64, 2048)
    assert (cfg.rope_sections, cfg.router, cfg.attn_impl,
            cfg.shared_experts, cfg.rescale_depth) \
        == ((16, 24, 24), "softmax", None, 0, 48)
    tree = jax.eval_shape(lambda key: transformer.init_params(
        family._DeviceRandn(key), cfg), jax.random.PRNGKey(0))

    def count(part):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(part))

    stated = config["parameters"]
    norm = 2048
    assert count(tree["dsa"]) == 6 * (stated["attention"]
                                      + stated["indexer"] + norm)
    assert count([tree["dsa"][m] for m in ("wq_idx", "wk_idx", "ww_idx")]) \
        == 6 * stated["indexer"] == 6 * 2_260_992
    assert count(tree["moe"]) == 6 * (stated["router"]
                                      + stated["experts_held"] + norm)
    assert stated["experts_held"] == 8 * stated["one_expert"]
    assert stated["two_norms"] == 2 * norm
    assert stated["layer"] == (stated["attention"] + stated["indexer"]
                               + stated["two_norms"] + stated["router"]
                               + stated["experts_held"]) == 59_150_592
    assert count((tree["embed"], tree["head"], tree["ln_f"])) \
        == stated["embedding_head_final_norm"]
    assert count(tree) == stated["total"] == 432_696_832 \
        == 6 * stated["layer"] + stated["embedding_head_final_norm"]
    assert stated["static_bytes"] == 16 * count(tree)


# ---------------------------------------------------------------------------
# The toy cell through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dsa_root(toy_root):
    """``test_benchmark_harness.toy_root`` (this module's own copy)
    with a toy configuration of this family, a cell, and the real
    manifest's per-layer entries for the real cell."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        toy = json.load(f)
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    toy["configs"].append({
        "name": "toy-dsa", "source": "none",
        "file": "benchmark/configs/toy-dsa.json", "reduced": [],
        "why": "CPU tests"})
    toy["workloads"].append({
        "name": "toy-dsa.s64", "config": "toy-dsa",
        "traffic": "toy-dsa-s64", "chips": 1,
        "why": "CPU tests: attention over an indexer's selection"})
    for metric in toy["end_to_end"]:
        if metric["name"] == "tokens_per_s_per_chip":
            metric["workloads"].append("toy-dsa.s64")
    for name in NEW_METRICS + SHARED_METRICS + PART_METRICS:
        entry = dict(real[name])
        if "workloads" in entry:
            assert CELL in entry["workloads"], name
            entry["workloads"] = ["toy-dsa.s64"]
        toy["per_layer"].append(entry)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(toy, f)
    return toy_root


def test_toy_cell_gives_the_new_metrics(dsa_root, capfd):
    """The traced line of a run through ``run.run``: correct, every
    metric of the cell, the indexed sub-layers' share with the indexer's
    inside it, the five parts adding up to 1 with every sub-layer
    recomputed, the share of a causal call's pairs that the selections
    keep, and the expert layer's readers on this family's
    ``expert_cost``."""
    code, line, cell = run_cell(dsa_root, capfd, "toy-dsa.s64", True)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert sum(got[m]["value"] for m in PART_METRICS) \
        == pytest.approx(1.0, abs=1e-6)
    assert 0 < got["dsa_index_share"]["value"] \
        < got["dsa_share"]["value"] < 1
    assert got["dsa_share"]["unit"] == "frac_of_busy"
    assert got["dsa_attend_roofline"]["value"] > 0
    assert got["dsa_index_roofline"]["value"] > 0
    assert got["dsa_attend_roofline"]["unit"] == "%"
    # 904 of 2,080 pairs in both layers
    assert got["dsa_kept_share"]["value"] == pytest.approx(904 / 2080)
    assert 0 < got["moe_share"]["value"] < 1
    assert got["moe_experts_roofline"]["value"] > 0
    assert 1.0 <= got["moe_load_max_over_mean"]["value"] <= 4.0
    with open(os.path.join(cell.out_dir, "records.json")) as f:
        reference = json.load(f)[0]["reference"]
    assert reference["ok"] and reference["selection"]["ok"]
    assert len(reference["pairs_sent"]) == 2
    assert len(reference["selections"]) == 2


def test_readers_give_nothing_where_the_program_has_no_such_name(
        dsa_root, monkeypatch):
    """Laid over the parent's checkout — no ``hvd_dsa`` scope, no
    ``hvd_dsa_select`` record, no ``kernel_costs`` of it — the five new
    readers return nothing and none raises."""
    import sys

    cell = manifest.load_cell("toy-dsa.s64",
                              os.path.join(dsa_root, "BENCHMARK.json"))
    trace = reduce.Trace({"chip": [reduce.Op("fusion.1", 0, 10)]}, [], 1)
    peaks = manifest.load_peaks(cell, "TPU v5 lite")
    names = {"fusion.1": "jit(step)/jvp(hvd_swa)/dot_general"}
    costs = ({"flash_attn": {"flops": 1.0, "bytes": 1.0}},
             {"dsa_attend": {"flops": 1.0, "bytes": 1.0},
              "dsa_index": {"flops": 1.0, "bytes": 1.0}})
    flight = sys.modules.get("horovod_tpu.runtime.flight")
    if flight is not None:
        monkeypatch.setattr(flight.recorder(), "snapshot", lambda: [])
    for found in (None, names):
        monkeypatch.setattr(scopes, "names_of", lambda cell: found)
        for cost in costs:
            counters = {"peaks": peaks, "kernel_costs": cost}
            for name in NEW_METRICS:
                read = manifest.load_layer_reader(cell, name)
                assert read(trace, counters, cell) is None, name


def test_each_reader_reads_its_own_scope(dsa_root, monkeypatch):
    """``dsa_share`` is the time under ``hvd_dsa``, ``dsa_index_share``
    the part of it under ``hvd_dsa_index``; ``dsa_attend_roofline``
    divides the family's ``dsa_attend`` cost by the time under
    ``hvd_attn`` (kernels and what stands between them),
    ``dsa_index_roofline`` the ``dsa_index`` cost by the time under
    ``hvd_dsa_index``."""
    cell = manifest.load_cell("toy-dsa.s64",
                              os.path.join(dsa_root, "BENCHMARK.json"))
    family = manifest.load_family(cell)

    def kernel(name, start, end):
        return reduce.Op(f"%{name} = bf16[8]{{0}} custom-call(%q), "
                         + reduce.MOSAIC_TARGET, start, end)

    ops = [reduce.Op("fusion.1", 0, 10), reduce.Op("fusion.2", 10, 40),
           kernel("hvd_flash_fwd_sel.3", 40, 50),
           reduce.Op("fusion.4", 50, 55),
           kernel("hvd_flash_bwd_dq_sel.5", 55, 75),
           reduce.Op("fusion.6", 75, 100)]
    names = {
        "fusion.1": "jit(step)/jvp(hvd_dsa)/dot_general",
        "fusion.2": "jit(step)/jvp(hvd_dsa)/hvd_dsa_index/while/body/ge",
        "hvd_flash_fwd_sel.3":
            "jit(step)/jvp(hvd_dsa)/hvd_attn/hvd_flash_fwd_sel",
        "fusion.4": "jit(step)/transpose(jvp(hvd_dsa))/hvd_attn/mul",
        "hvd_flash_bwd_dq_sel.5":
            "jit(step)/transpose(jvp(hvd_dsa))/hvd_attn/hvd_flash_bwd_dq_sel",
        "fusion.6": "jit(step)/transpose(jvp(hvd_moe))/dot_general"}
    trace = reduce.Trace({"chip": ops}, [], 1)
    monkeypatch.setattr(scopes, "names_of", lambda cell: names)
    counters = {"peaks": manifest.load_peaks(cell, "TPU v5 lite"),
                "kernel_costs": family.kernel_costs(cell.config, cell.job)}

    def read(name):
        return manifest.load_layer_reader(cell, name)(trace, counters, cell)

    assert read("dsa_share") == pytest.approx(75 / 100)
    assert read("dsa_index_share") == pytest.approx(30 / 100)
    assert read("dsa_attend_roofline") == pytest.approx(roofline.percent(
        counters["kernel_costs"]["dsa_attend"], counters["peaks"], 35e-9))
    assert read("dsa_index_roofline") == pytest.approx(roofline.percent(
        counters["kernel_costs"]["dsa_index"], counters["peaks"], 30e-9))
