"""The sharded path on a small 8-device host mesh (subprocess): a train step
and the decode step lower and compile, and the cache shardings of
``parallel.sharding``."""
import pytest

from tests._subproc import run_with_devices


@pytest.mark.slow
def test_small_mesh_lower_compile_smoke():
    """A reduced config lowers+compiles on a (2 pod, 2 data, 2 model) mesh —
    the multi-pod pattern end-to-end, without the 512-device cost."""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from repro.configs.registry import get
from repro.models import transformer
from repro.models.config import Runtime
from repro.parallel import sharding as shd
from repro import optim
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get("granite-3-8b").smoke
rt = Runtime(remat=True, xent_chunk=16, moe_groups=4)
rules = shd.lm_rules(fsdp=True)
with shd.use_sharding(mesh, rules):
    params = jax.eval_shape(lambda k: transformer.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    psh = shd.param_shardings(params, mesh, rules)
    ocfg = optim.AdamWConfig()
    ost = jax.eval_shape(lambda p: optim.init_state(p, ocfg), params)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    bsh = {k: NamedSharding(mesh, P(("pod", "data"), None)) for k in batch}

    def step(p, s, b):
        (l, m), g = jax.value_and_grad(
            lambda q: transformer.train_loss(q, b, cfg, rt), has_aux=True)(p)
        np_, ns = optim.apply_update(p, g, s, ocfg)
        return np_, ns, l

    osh = shd.opt_shardings(params, ost, mesh, rules)
    compiled = jax.jit(step, in_shardings=(psh, osh, bsh)).lower(
        params, ost, batch).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0
    txt = compiled.as_text()
    assert "all-reduce" in txt or "reduce-scatter" in txt  # DP gradient sync
print("COMPILED")
""", n_devices=8, timeout=480)
    assert "COMPILED" in out


def test_cache_shardings_divisibility():
    import jax
    import jax.numpy as jnp
    from repro.parallel.sharding import cache_shardings
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shapes = {"l0": {"k": jax.ShapeDtypeStruct((2, 1, 7, 3, 8), jnp.bfloat16)}}
    sh = cache_shardings(shapes, mesh, ("data",))
    # batch=1 and seq=7 not divisible by anything >1 -> fully replicated
    spec = sh["l0"]["k"].spec
    assert all(s is None for s in spec)


@pytest.mark.parametrize("mode", ["seq", "head_dim"])
def test_small_mesh_decode_step_lowers(mode):
    """The decode step, with the stacked cache carried through the layer scan
    and sharded by ``sharding.cache_shardings``, compiles on a (2 pod,
    2 data, 2 model) mesh for a dense and a hybrid period, donating the
    cache in place."""
    out = run_with_devices(f"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import get
from repro.launch.mesh import make_mesh
from repro.models import transformer
from repro.models.config import Runtime
from repro.parallel import sharding as shd

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = shd.lm_rules()
batch = P(("pod", "data"))
for arch in ("granite-3-8b", "jamba-v0.1-52b"):
    cfg = get(arch).smoke
    rt = Runtime(moe_groups=4, cache_shard={mode!r})
    with shd.use_sharding(mesh, rules):
        params = jax.eval_shape(lambda k: transformer.init_lm(k, cfg),
                                jax.random.PRNGKey(0))
        cache = jax.eval_shape(
            lambda: transformer.init_cache(cfg, 8, 256, cfg.cdtype))
        c_sh = shd.cache_shardings(cache, mesh, ("pod", "data"), mode={mode!r})
        tok_sh = NamedSharding(mesh, P(("pod", "data"), None))
        step = jax.jit(
            lambda p, c, t, q: transformer.decode_step(p, c, t, q, cfg, rt),
            in_shardings=(shd.param_shardings(params, mesh, rules), c_sh,
                          tok_sh, NamedSharding(mesh, batch)),
            out_shardings=(tok_sh, c_sh), donate_argnums=(1,))
        compiled = step.lower(params, cache,
                              jax.ShapeDtypeStruct((8, 1), jnp.int32),
                              jax.ShapeDtypeStruct((8,), jnp.int32)).compile()
        cache_bytes = sum(a.size * a.dtype.itemsize
                          for a in jax.tree_util.tree_leaves(cache)) // 8
        assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
print("COMPILED")
""", n_devices=8, timeout=480)
    assert "COMPILED" in out
