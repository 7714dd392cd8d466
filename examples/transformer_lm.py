"""Flagship transformer LM with full TPU-era parallelism — the
capability the GPU-era reference lacks (SURVEY.md §2.7 ❌ rows): tensor
parallel, pipeline parallel, sequence parallel (ring attention) and
expert parallel, all expressed as shardings over one `jax.sharding.Mesh`
and compiled by XLA into ICI collectives.

It runs on whatever devices JAX gives it: with no mesh flags the
(dp, tp, sp) mesh is sized from the visible device count (one chip →
1x1x1, a four-chip host → tp=2, sp=2), and any axis named on the
command line is taken as given (the rest default to 1)::

    python examples/transformer_lm.py                 # all visible chips
    python examples/transformer_lm.py --dp 2 --tp 2   # four chips, dp x tp

For a rehearsal without an accelerator, ask for the CPU and force host
devices::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_lm.py --dp 2 --tp 2 --sp 2
"""

try:
    import horovod_tpu  # noqa: F401
except ImportError:  # running from a source checkout
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import argparse
import time

import numpy as np

from horovod_tpu.common.platform import ensure_platform

# HOROVOD_PLATFORM and the compile cache, before any backend init
ensure_platform()


def main() -> None:
    p = argparse.ArgumentParser()
    for axis in ("dp", "pp", "tp", "sp"):
        p.add_argument(f"--{axis}", type=int, default=None)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--experts", type=int, default=0,
                   help="experts a dp rank holds in every layer after "
                        "the first (dropless sigmoid top-2 over dp x "
                        "this many, plus a shared expert; 0 = dense)")
    p.add_argument("--layer-pattern", default="",
                   help="one letter a layer, each layer ONE sub-layer: "
                        "M a Mamba-2 mixer, * grouped-query attention "
                        "without positions, E an expert layer of relu^2 "
                        "experts (with --experts; e.g. MEM*E); S and G "
                        "gated grouped-query attention with QK-norm, S "
                        "under a sliding window of seq / 4 with rotary "
                        "positions, G over the whole past with none, D "
                        "a dense SwiGLU FFN; with S, G or D every "
                        "sub-layer also gets a norm after it and the "
                        "experts are SwiGLU (e.g. SDSESESEGE); I "
                        "grouped-query attention over the seq / 4 keys "
                        "an indexer of 2 heads scores highest for each "
                        "query, with QK-norm and rotary positions in "
                        "three sections, and with it a softmax router "
                        "over SwiGLU experts, no shared one (e.g. "
                        "IEIE). Replaces "
                        "--n-layers; dp only (no pp, tp or sp)")
    p.add_argument("--pp-schedule", default="gpipe",
                   choices=["gpipe", "interleaved"],
                   help="pipeline schedule when pp > 1 (interleaved = "
                        "Megatron virtual stages, ~pp-virtual-fold "
                        "smaller bubble)")
    p.add_argument("--pp-virtual", type=int, default=1,
                   help="virtual chunks per pipeline rank "
                        "(interleaved schedule)")
    args = p.parse_args()
    if args.pp_virtual > 1 and (args.pp or 1) <= 1:
        raise SystemExit(
            "--pp-virtual > 1 needs --pp > 1: without pipeline ranks "
            "there is nothing to interleave (the run would just train "
            "a deeper dense model)")

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params,
                                                make_train_step,
                                                shard_params)
    from horovod_tpu.parallel.mesh import AXES, factor_devices, make_mesh

    devices = jax.devices()
    named = {a: getattr(args, a) for a in AXES
             if getattr(args, a) is not None}
    axes = ({a: named.get(a, 1) for a in AXES} if named
            else factor_devices(len(devices)))
    dp, pp, tp = axes["dp"], axes["pp"], axes["tp"]
    n = int(np.prod(list(axes.values())))
    if len(devices) < n:
        raise SystemExit(f"need {n} devices for dp*pp*tp*sp, "
                         f"have {len(devices)} "
                         f"({devices[0].platform})")

    if "E" in args.layer_pattern and not args.experts:
        raise SystemExit("an E layer needs --experts")
    experts = dict(n_experts=dp * args.experts, experts_held=args.experts,
                   experts_per_token=2, d_expert=args.d_model,
                   shared_experts=1) if args.experts else {}
    if args.layer_pattern:
        # a hybrid stack: 4 state-space heads in 2 groups, 2 key/value
        # heads under the 4 query heads, two-matrix experts
        kinds = dict(layer_pattern=args.layer_pattern, tied_head=False,
                     n_kv_heads=2, ssm_heads=4,
                     ssm_head_dim=2 * args.d_model // 4, ssm_groups=2,
                     ssm_state=16, ssm_chunk=min(64, args.seq),
                     expert_form="relu2", **experts)
        if set(args.layer_pattern) & set("SGD"):
            # a window / full attention stack: a quarter of the sequence
            # in the window, a norm after every sub-layer, the embedding
            # times sqrt(d), SwiGLU experts
            kinds.update(window=max(1, args.seq // 4), post_norm=True,
                         embed_scale=args.d_model ** 0.5,
                         expert_form="swiglu")
        if "I" in args.layer_pattern:
            # a sparse-attention stack: a quarter of the sequence kept
            # for each query, positions of three (here equal) components
            pairs = args.d_model // 8
            kinds.update(index_heads=2, index_head_dim=16,
                         index_topk=max(1, args.seq // 4),
                         rope_sections=(pairs - 2 * (pairs // 3),
                                        pairs // 3, pairs // 3),
                         router="softmax", shared_experts=0,
                         expert_form="swiglu")
    else:
        kinds = dict(mlp="swiglu", n_dense_layers=1,
                     **experts) if experts else {}
    cfg = TransformerConfig(
        vocab=1024, d_model=args.d_model,
        n_heads=max(4, 2 * tp), head_dim=args.d_model // 4,
        n_layers=args.n_layers * pp * args.pp_virtual,
        d_ff=4 * args.d_model, max_seq=args.seq, **kinds,
        pp_microbatches=2 if pp > 1 else 1,
        pp_schedule=args.pp_schedule, pp_virtual=args.pp_virtual)
    mesh = make_mesh(**axes, devices=devices[:n])
    print(f"mesh: {axes} "
          f"({n} of {len(devices)} {devices[0].platform} devices)")

    params = shard_params(init_params(np.random.RandomState(0), cfg,
                                      ep=dp), cfg, mesh)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)

    rng = np.random.RandomState(1)
    sh = NamedSharding(mesh, P("dp", "sp"))
    tokens = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (args.batch, args.seq)), jnp.int32), sh)
    targets = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (args.batch, args.seq)), jnp.int32), sh)

    params, opt_state, loss = step(params, opt_state, tokens, targets)
    jax.block_until_ready(params)  # compile + first step
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        if i % 5 == 0:
            print(f"step {i} loss {float(loss):.4f}")
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    toks = args.batch * args.seq * args.steps
    print(f"{toks / dt:.0f} tokens/sec ({dt / args.steps * 1000:.1f} "
          f"ms/step)")


if __name__ == "__main__":
    main()
