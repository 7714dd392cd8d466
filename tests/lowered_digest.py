"""sha256 of each LM cell's train step lowered for a described v5e, with
every Mosaic payload parsed and printed without locations (the method of
PR 31): run from the root of a checkout; prints one line a cell."""
import base64, hashlib, json, os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax, optax
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P
from jax._src.lib.mlir import ir
from jax._src.lib import tpu  # registers the tpu dialect
from benchmark import manifest
from horovod_tpu.models import transformer
from horovod_tpu.parallel.mesh import make_mesh

jax.config.update("jax_enable_compilation_cache", False)
chip = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
jax.default_backend = lambda: "tpu"


def lowered_text(name):
    cell = manifest.load_cell(name)
    family = manifest.load_family(cell)
    config, job = cell.config, cell.job
    if hasattr(family, "_kwargs"):
        cfg = transformer.TransformerConfig(**family._kwargs(config, job))
    else:
        cfg = transformer.TransformerConfig(
            max_seq=max(config["n_positions"], job["seq"]),
            dtype=config["compute_dtype"], **family._sizes(config))
    mesh = make_mesh(**job["mesh"], devices=[chip])
    here = NamedSharding(mesh, P())
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=here), tree)
    opt = optax.adamw(config["optimizer"]["learning_rate"])
    rng = getattr(family, "_DeviceRandom", None) or family._DeviceRandn
    params = jax.eval_shape(lambda key: transformer.init_params(rng(key), cfg),
                            jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq"]), "int32", sharding=here)
    return transformer.make_train_step(cfg, mesh, opt).lower(
        shapes(params), shapes(jax.eval_shape(opt.init, params)), ids, ids).as_text()


def without_locations(text):
    """Each Mosaic payload (base64 MLIR bytecode in the call's
    backend_config) replaced by its module printed without locations."""
    count = 0

    def payload(match):
        nonlocal count
        count += 1
        ctx = ir.Context()
        tpu.register_dialect(ctx) if hasattr(tpu, "register_dialect") else None
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(match.group(1)), ctx)
        printed = module.operation.get_asm(enable_debug_info=False)
        return r'\22body\22: \22' + printed + r'\22'

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', payload, text), count


for name in sys.argv[1:]:
    text = lowered_text(name)
    plain, payloads = without_locations(text)
    print(json.dumps({"cell": name, "bytes": len(text), "payloads": payloads,
                      "sha256": hashlib.sha256(plain.encode()).hexdigest(),
                      "sha256_as_lowered": hashlib.sha256(text.encode()).hexdigest()}), flush=True)
