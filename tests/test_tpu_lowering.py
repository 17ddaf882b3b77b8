"""The characterization kernels compile for a TPU v5e chip.

The Pallas interpreter accepts programs Mosaic refuses (unaligned slices,
scalar loads from vector memory, 1-D vector layouts), so every kernel the
characterization plans run on the chip is compiled here for a described
v5e topology: nothing runs, but what the chip's compiler would refuse fails.
The serving decode step is compiled the same way, to check that it updates
the KV cache in place. The topology is described inside a fixture only,
never at import time: one process at a time may load the TPU library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.membench import build_ring
from repro.inkernel.fused import FUSED_KERNELS, FUSED_LENS, build_fused
from repro.inkernel.measure import CHASE_LENS
from repro.kernels.alu_chain import alu_chain
from repro.kernels.chase import VMEM_BUDGET_BYTES, chase, select_memory_space


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    # a described-chip compile is written to the persistent cache but cannot
    # be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_for_chip(fn, args, sharding) -> str:
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
              for a in args]
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("ws", [VMEM_BUDGET_BYTES >> 8, VMEM_BUDGET_BYTES,
                                VMEM_BUDGET_BYTES << 2])
def test_chase_lowers_at_ladder_rungs(one_chip, ws):
    """The budget rung is the largest VMEM-resident ring; above it the ring
    streams from HBM."""
    ring, start = build_ring(ws)
    space = select_memory_space(ring.size * 4)
    assert space == ("vmem" if ws <= VMEM_BUDGET_BYTES else "any")
    fn = functools.partial(chase, steps=CHASE_LENS.chip[1], interpret=False,
                           memory_space=space)
    assert "tpu_custom_call" in _compile_for_chip(fn, (ring, start), one_chip)


@pytest.mark.parametrize("op", ["fma", "rsqrt"])
def test_alu_chain_lowers(one_chip, op):
    x = jnp.ones((8, 128), jnp.float32)
    fn = functools.partial(alu_chain, n=64, op=op, interpret=False)
    assert "tpu_custom_call" in _compile_for_chip(fn, (x, x), one_chip)


@pytest.mark.parametrize("name", FUSED_KERNELS)
def test_fused_kernels_lower(one_chip, name):
    for n in FUSED_LENS.chip:
        fn, args = build_fused(name, n, interpret=False)
        assert "tpu_custom_call" in _compile_for_chip(fn, args, one_chip)


def test_inkernel_chains_lower(one_chip, monkeypatch):
    """Every registry row the in-kernel plan keeps on a TPU compiles there;
    float16 rows (no v5e vector layout) stay on the dispatch path."""
    from repro import inkernel
    from repro.inkernel import factory

    monkeypatch.setattr(factory, "use_interpret", lambda: False)
    specs = inkernel.supported_specs()
    assert specs and not any(s.dtype == "float16" for s in specs)
    for spec in specs:
        carry, operands = inkernel.tiles(spec)
        fn = inkernel.build_chain(spec, inkernel.INKERNEL_LENS.chip[1],
                                  interpret=False)
        assert "tpu_custom_call" in _compile_for_chip(
            fn, (carry,) + tuple(operands), one_chip), spec.name


# ------------------------------------------------------- decode step, in place
_INSTR = re.compile(
    r"\s*(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\((%[\w.\-]+)?")


def _cache_movers(hlo: str, shapes: set) -> list:
    """``(op, name)`` of each ``copy``, ``dynamic-slice`` and
    ``dynamic-update-slice`` outside a fused computation whose result has
    one of ``shapes``; a fusion counts as the op at its root, through
    bitcasts."""
    comps: dict = {}
    cur = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head and line.rstrip().endswith("{"):
            cur = comps.setdefault(head.group(1), {"instrs": {}, "root": None})
            continue
        m = _INSTR.match(line)
        if cur is None or not m:
            continue
        root, name, dims, op, operand = m.groups()
        called = re.search(r"calls=%([\w.\-]+)", line)
        cur["instrs"][name] = (
            tuple(int(d) for d in dims.split(",") if d), op,
            operand[1:] if operand else None,
            called.group(1) if called and op == "fusion" else None)
        if root:
            cur["root"] = name
    # fusions with a tuple result are not parsed above, but call one too
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo))

    def root_op(comp: str) -> str:
        c = comps[comp]
        if c["root"] not in c["instrs"]:
            return "tuple"
        shape, op, operand, called = c["instrs"][c["root"]]
        while op == "bitcast" and operand in c["instrs"]:
            shape, op, operand, called = c["instrs"][operand]
        return root_op(called) if called else op

    found = []
    for cname, c in comps.items():
        if cname in fused:
            continue
        for name, (shape, op, _, called) in c["instrs"].items():
            op = root_op(called) if called else op
            if shape in shapes and op in ("copy", "dynamic-slice",
                                          "dynamic-update-slice"):
                found.append((op, name))
    return found


def _compile_decode_step(one_chip, periods, kv_heads, per_row, rt):
    """The donated decode step of a bfloat16 decoder of ``periods`` layers
    (32 heads of 128 over ``kv_heads``), 32 slots of 4,096 positions,
    compiled for the described chip; returns it and one period's K shape."""
    from repro.models import transformer
    from repro.models.config import ModelConfig

    slots, max_len, hd = 32, 4096, 128
    cfg = ModelConfig(name="guard", family="dense", n_layers=periods,
                      d_model=4096, n_heads=32, n_kv_heads=kv_heads,
                      head_dim=hd, d_ff=2048, vocab_size=1024,
                      param_dtype="bfloat16", compute_dtype="bfloat16",
                      tie_embeddings=False)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    params = placed(jax.eval_shape(
        lambda: transformer.init_lm(jax.random.PRNGKey(0), cfg)))
    cache = placed(jax.eval_shape(
        lambda: transformer.init_cache(cfg, slots, max_len, cfg.cdtype)))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,) if per_row else (), jnp.int32,
                               sharding=one_chip)
    step = jax.jit(lambda p, c, t, q: transformer.decode_step(
        p, c, t, q, cfg, rt), donate_argnums=(1,))
    return (step.lower(params, cache, tokens, pos).compile(),
            (slots, max_len, kv_heads, hd))


def _reads_the_stacked_cache_in_place(compiled, periods, period):
    """No period of the cache is written back, copied or sliced out, the
    step's temporaries stay below one period's K, and attention is the
    stacked-cache kernel, once a layer."""
    text = compiled.as_text()
    period_bytes = 2 * period[0] * period[1] * period[2] * period[3]
    assert compiled.memory_analysis().temp_size_in_bytes < period_bytes
    movers = _cache_movers(text, {period, (1,) + period, (periods,) + period})
    assert movers == []
    return len(re.findall(r"%flash_decode_stacked[.\d]* = .*custom-call", text))


@pytest.mark.parametrize("per_row", [True, False], ids=["per_row", "scalar"])
def test_decode_step_updates_the_stacked_cache_in_place(one_chip, per_row):
    """The donated decode step writes each token into the stacked KV cache
    in place and its attention reads each period where it lies, for
    per-row and for scalar positions: no period's K/V is written back,
    copied or sliced out, and the layer scan calls the stacked-cache
    kernel."""
    from repro.models.config import Runtime

    periods = 4
    compiled, period = _compile_decode_step(one_chip, periods, 4, per_row,
                                            Runtime())
    assert _reads_the_stacked_cache_in_place(compiled, periods, period) == 1


def test_decode_step_unrolled_at_jamba_widths(one_chip):
    """Periods unrolled (``scan_layers`` off, as the hybrid cell runs) at
    Jamba's attention widths, 8 KV heads of 32 query heads: each period's
    attention is the kernel, given its period as a constant."""
    from repro.models.config import Runtime

    periods = 2
    compiled, period = _compile_decode_step(one_chip, periods, 8, True,
                                            Runtime(scan_layers=False))
    assert _reads_the_stacked_cache_in_place(compiled, periods,
                                             period) == periods


def test_flash_decode_probe_is_not_the_serving_kernel(one_chip):
    """The characterization's ``flash_decode`` probe lowers its own kernel,
    not the serving step's stacked-cache one."""
    fn, args = build_fused("flash_decode", FUSED_LENS.chip[0], interpret=False)
    text = _compile_for_chip(fn, args, one_chip)
    assert "tpu_custom_call" in text
    assert "flash_decode_stacked" not in text


def test_moe_experts_lowers_at_jamba_widths(one_chip):
    """The decode expert kernel at Jamba's widths (32 rows, 8 held experts
    of 4096 x 14336): it lowers, keeps its name for the trace, and needs no
    temporary (its weights are read where they lie)."""
    from repro.kernels.moe_experts import moe_experts

    n, d, f, e = 32, 4096, 14336, 8
    shapes = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((n, d), jnp.bfloat16), ((n, e), jnp.float32),
        ((e, d, f), jnp.bfloat16), ((e, d, f), jnp.bfloat16),
        ((e, f, d), jnp.bfloat16))]
    compiled = jax.jit(functools.partial(moe_experts, interpret=False)).lower(
        *shapes).compile()
    assert re.search(r"%moe_experts\.\d+ = .*custom-call", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_mamba_scan_lowers_returning_its_state(one_chip):
    from repro.kernels.mamba_scan import mamba_scan

    b, s, dm, n = 1, 256, 256, 16
    args = [jnp.zeros(sh, jnp.float32) for sh in (
        (b, s, dm), (b, s, dm), (dm, n), (b, s, n), (b, s, n), (dm,))]
    fn = functools.partial(mamba_scan, chunk=128, interpret=False,
                           return_state=True)
    assert "tpu_custom_call" in _compile_for_chip(fn, args, one_chip)


# ------------------------------------------------------ prefill, flash kernel
def _compile_prefill(one_chip, prompt_len, mesh=None):
    """The prefill of a 2-layer bfloat16 decoder at Yi-9B's widths (d 4096,
    32 heads of 128 over 4, MLP 11008) with the serving adapter's
    ``Runtime(remat=False)``, one prompt of ``prompt_len`` tokens, compiled
    for the described chip, under ``mesh`` if one is given."""
    import contextlib

    from repro.models import transformer
    from repro.models.config import ModelConfig, Runtime
    from repro.parallel import sharding

    cfg = ModelConfig(name="yi-guard", family="dense", n_layers=2,
                      d_model=4096, n_heads=32, n_kv_heads=4, head_dim=128,
                      d_ff=11008, vocab_size=1024, param_dtype="bfloat16",
                      compute_dtype="bfloat16", tie_embeddings=False)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: transformer.init_lm(jax.random.PRNGKey(0), cfg)))
    tokens = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32, sharding=one_chip)
    last = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    ctx = (sharding.use_sharding(mesh, sharding.lm_rules()) if mesh is not None
           else contextlib.nullcontext())
    with ctx:
        return jax.jit(lambda p, t, l: transformer.prefill(
            p, cfg, Runtime(remat=False), tokens=t, last_positions=l)).lower(
                params, tokens, last).compile()


def _flash_calls(text: str) -> int:
    return len(re.findall(r"%flash_attention[.\d]* = .*custom-call", text))


def _score_buffers(text: str, s: int) -> list:
    return re.findall(rf"f32\[[\d,]*{s},{s}\]", text)


@pytest.mark.parametrize("prompt_len", [1536, 1000], ids=["rag_1536", "ragged_1000"])
def test_prefill_attention_is_the_flash_kernel(one_chip, prompt_len):
    """On a TPU with no mesh, prefill's causal self-attention is the flash
    kernel, at a prompt length that is a multiple of 128 and one that is
    not: no float32 [S,S] score tensor is built, and the temporaries stay
    far below the 302 MB one layer's scores took at 1,536 tokens."""
    compiled = _compile_prefill(one_chip, prompt_len)
    text = compiled.as_text()
    assert _flash_calls(text) >= 1
    assert _score_buffers(text, prompt_len) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_prefill_attention_under_a_mesh_stays_on_xla(one_chip):
    """Under an active sharding mesh, which the kernel does not partition,
    ``auto`` keeps XLA's attention: the float32 [S,S] scores come back."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    text = _compile_prefill(one_chip, 512, mesh).as_text()
    assert _flash_calls(text) == 0
    assert _score_buffers(text, 512)


def test_training_gradient_lowers_through_the_flash_kernel(one_chip):
    """Training's forward takes the kernel too; ``pallas_call`` has no
    reverse rule, so the gradient compiles only through the wrapper's
    blockwise backward pass."""
    from repro.models import transformer
    from repro.models.config import ModelConfig, Runtime

    cfg = ModelConfig(name="train-guard", family="dense", n_layers=2,
                      d_model=512, n_heads=4, n_kv_heads=2, head_dim=128,
                      d_ff=1024, vocab_size=1024, param_dtype="bfloat16",
                      compute_dtype="bfloat16", tie_embeddings=False)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: transformer.init_lm(jax.random.PRNGKey(0), cfg)))
    tokens = jax.ShapeDtypeStruct((2, 384), jnp.int32, sharding=one_chip)
    grad = jax.jit(jax.grad(lambda p, t: transformer.train_loss(
        p, {"tokens": t, "labels": t}, cfg, Runtime())[0]))
    assert _flash_calls(grad.lower(params, tokens).compile().as_text()) >= 1
