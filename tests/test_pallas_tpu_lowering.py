"""Every Pallas entry point, and the serving engine's unified program,
must LOWER for a TPU — checked here on the CPU by cross-lowering with
jax.export(platforms=["tpu"]) at the widths the chip runs.

This is jaxpr -> Mosaic MLIR only: libtpu's Mosaic compiler does not run,
so VMEM/SMEM limits and tiling stay chip_smoke.py's business. What this
catches without a chip is the class of error a kernel body can carry for
months behind interpret=True — e.g. "Cannot store scalars to VMEM".
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.models import GPT2ForCausalLM, gpt2_774m_config
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.nn import dot_product_attention as dpa
from mxnet_tpu.serving import Request, ServingEngine
from mxnet_tpu.telemetry import cost

# GPT-2 774M attention widths, the engine's page size and slot count;
# pools of LAYERS layers packed (LAYERS, pages, PAGE, H*D), read at LAYER
H, D, PAGE, SLOTS, PAGES_PER_SLOT = 20, 64, 64, 8, 16
LAYERS, LAYER = 3, 2


def _lowers_to_mosaic(fn, *args):
    """Cross-lower fn for TPU; return how many Mosaic kernels it holds."""
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    n = exp.mlir_module().count("tpu_custom_call")
    assert n, "lowered for TPU without a single Mosaic kernel"
    return n


@pytest.fixture
def on_tpu(monkeypatch):
    """impl='auto' resolves from jax.default_backend(); answer as the
    chip would, so the test walks the selection the chip walks."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _span_args(sq, qdtype, page_dtype):
    n = SLOTS * PAGES_PER_SLOT
    args = [_sds((SLOTS, sq, H, D), qdtype),
            _sds((LAYERS, n, PAGE, H * D), page_dtype),
            _sds((LAYERS, n, PAGE, H * D), page_dtype),
            _sds((SLOTS, PAGES_PER_SLOT), "int32"),
            _sds((SLOTS,), "int32"), _sds((SLOTS,), "int32")]
    if jnp.dtype(page_dtype) == jnp.int8:
        args += [_sds((LAYERS, n, H), "float32")] * 2
    return args


@pytest.mark.parametrize("sq,qdtype,page_dtype", [
    (64, "bfloat16", "bfloat16"), (4, "bfloat16", "bfloat16"),
    (64, "float32", "float32"),
    (64, "bfloat16", "int8"), (1, "bfloat16", "int8")])
def test_ragged_span_attention_lowers(on_tpu, sq, qdtype, page_dtype):
    def fn(q, kp, vp, table, lens, qc, ks=None, vs=None):
        return pa.ragged_span_attention(q, kp, vp, table, lens, q_counts=qc,
                                        k_scale=ks, v_scale=vs, layer=LAYER)

    from mxnet_tpu.ops.kernel_paths import TILES
    before = dict(TILES)
    assert _lowers_to_mosaic(fn, *_span_args(sq, qdtype, page_dtype)) == 1
    # one call, built with the block the adaptive rule documents: the 16
    # pages a slot has, 1024 keys a grid step, at every row count
    assert {k: n - before.get(k, 0) for k, n in TILES.items()
            if n != before.get(k, 0)} == {
        ("ragged_span_attention", f"pages=16,keys=1024,rows={sq}"): 1}


def test_ragged_decode_attention_is_the_sq1_span_call(on_tpu):
    q, kp, vp, table, lens, _ = _span_args(1, "bfloat16", "bfloat16")
    assert _lowers_to_mosaic(
        pa.ragged_decode_attention,
        _sds((SLOTS, H, D), "bfloat16"), kp, vp, table, lens) == 1
    assert not hasattr(pa, "_ragged_decode_kernel")


def test_auto_on_tpu_warns_before_taking_the_dense_reference(on_tpu):
    """5 heads of 64 (GPT-2 774M under tp=4) break the 128-lane rule:
    'auto' may use the dense reference, but never silently."""
    n = SLOTS * PAGES_PER_SLOT
    args = (_sds((SLOTS, 2, 5, D), "bfloat16"),
            _sds((LAYERS, n, PAGE, 5 * D), "bfloat16"),
            _sds((LAYERS, n, PAGE, 5 * D), "bfloat16"),
            _sds((SLOTS, PAGES_PER_SLOT), "int32"), _sds((SLOTS,), "int32"))
    with pytest.warns(UserWarning, match="dense XLA reference.*5\\*64"):
        exp = jax.export.export(jax.jit(pa.ragged_span_attention),
                                platforms=["tpu"])(*args)
    assert "tpu_custom_call" not in exp.mlir_module()


@pytest.mark.parametrize("layout,shape,causal,p_drop", [
    ("BTHD", (32, 512, 12, 64), False, 0.1),    # BERT-base, packed
    ("BTHD", (2, 1024, 20, 64), True, 0.0),     # GPT-2 774M, packed
    ("BHTD", (2, 12, 512, 64), False, 0.1),
])
def test_fused_attention_fwd_bwd_lowers(layout, shape, causal, p_drop):
    B, tk = shape[0], shape[1] if layout == "BTHD" else shape[2]

    def loss(q, k, v, mask, key):
        out = pa.fused_attention(q, k, v, mask=mask, causal=causal,
                                 dropout_p=p_drop, key=key, layout=layout)
        return out.astype(jnp.float32).sum()

    x = _sds(shape, "bfloat16")
    key = jax.eval_shape(lambda: jax.random.key(0))
    n = _lowers_to_mosaic(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          x, x, x, _sds((B, tk), "bool"), key)
    assert n == 2                                # forward + backward


def test_fused_attention_under_a_mesh_is_shard_mapped(on_tpu):
    """The SPMD partitioner refuses a Mosaic kernel ("cannot be
    automatically partitioned") — with a dp x tp mesh active the op must
    wrap the kernel in a shard_map itself (found by chip_smoke on four
    chips: a sharded BERT TrainStep did not lower)."""
    mesh = par.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    x = _sds((4, 512, 12, 64), "bfloat16")              # BTHD, BERT-base
    sharded = par.NamedSharding(mesh, par.PartitionSpec("dp", None, "tp"))

    def loss(q, k, v):
        return dpa.raw_fn(q, k, v, layout="BTHD").astype(jnp.float32).sum()

    with par.mesh_scope(mesh):
        exp = jax.export.export(
            jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    in_shardings=(sharded,) * 3), platforms=["tpu"])(x, x, x)
    assert exp.mlir_module().count("tpu_custom_call") == 2
    assert exp.nr_devices == 4


def test_flash_attention_long_context_lowers(on_tpu):
    x = _sds((1, 8, 8192, 128), "bfloat16")
    assert att.pallas_flash_eligible(x, x, None)

    def loss(q, k, v):
        return att.flash_attention_data(q, k, v, causal=True).astype(
            jnp.float32).sum()

    exp = jax.export.export(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                            platforms=["tpu"])(x, x, x)
    text = exp.mlir_module()
    assert "tpu_custom_call" in text
    assert "stablehlo.while" not in text         # not the lax.scan path


# -- the engine's unified program -------------------------------------------

@pytest.fixture(scope="module")
def gpt2_774m_one_layer():
    # 774M's attention and FFN widths; depth and vocabulary are cut
    # because they change no kernel and cost this CPU test its time
    cfg = gpt2_774m_config(dtype="bfloat16", dropout=0.0,
                           attention_dropout=0.0, num_layers=1,
                           vocab_size=1024)
    net = GPT2ForCausalLM(cfg)
    mx.rng.seed(0)
    net.initialize(mx.init.Normal(0.02))
    net.cast("bfloat16")
    return net, cfg


def _captured_unified(eng, cfg, do_sample):
    """The jitted unified program and the operands _dispatch hands it,
    taken at the CostedFunction seam (the dispatch is cut short with the
    one exception step() lets through)."""
    seen = {}

    def wrap(fn, name, cost_scale=1.0):
        def call(*args):
            seen["fn"], seen["args"] = fn, args
            raise cost.ProgramCompileError(name, RuntimeError("captured"))
        return call

    eng._wrap_program = wrap
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 70)
    eng.submit(Request(prompt.tolist(), 4, do_sample=do_sample, seed=1))
    with pytest.raises(cost.ProgramCompileError, match="captured"):
        eng.step()
    return seen["fn"], seen["args"]


@pytest.mark.parametrize("kw,do_sample", [
    ({}, False), ({}, True),
    ({"kv_dtype": "int8"}, False),
    ({"speculative": True}, True),
])      # prefix_cache and w8 weights change no kernel operand
def test_engine_unified_program_lowers(gpt2_774m_one_layer, kw, do_sample):
    net, cfg = gpt2_774m_one_layer
    eng = ServingEngine(net, num_slots=SLOTS, max_length=1024,
                        page_size=PAGE, attn_impl="pallas", **kw)
    fn, args = _captured_unified(eng, cfg, do_sample)
    exp = jax.export.export(fn, platforms=["tpu"])(*args)
    assert exp.mlir_module().count("tpu_custom_call") == cfg.num_layers


def test_engine_step_raises_when_the_unified_program_cannot_compile():
    """attn_impl='pallas' on a CPU: Mosaic has no CPU target, so the
    program cannot compile. step() must raise — not retry max_retries
    times per request and report status='failed'."""
    cfg = gpt2_774m_config(units=128, num_heads=2, num_layers=1,
                           vocab_size=64, max_length=128, dropout=0.0,
                           attention_dropout=0.0)
    net = GPT2ForCausalLM(cfg)
    net.initialize(mx.init.Normal(0.02))
    eng = ServingEngine(net, num_slots=2, max_length=128, page_size=64,
                        attn_impl="pallas")
    req = Request([1, 2, 3], 4)
    eng.submit(req)
    with pytest.raises(cost.ProgramCompileError) as err:
        eng.step()
    assert err.value.program.startswith(f"engine{eng._eid}/unified/")
    assert req.status != "failed"
    assert eng.stats["dispatch_retries"] == 0
    assert eng.stats["requests_failed"] == 0
