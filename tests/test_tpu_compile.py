"""The main path's kernels and serving programs, compiled for a v5e.

No chip is needed: the TPU compiler is installed and compiles for a
DESCRIBED ``v5e:2x2`` (on-chip-measurement guide, section 2, rehearsal
3). It refuses what the chip would refuse — a slice off the tiling, too
much VMEM, a program that does not fit HBM — which interpret mode never
shows. A compile that passes is not a chip run; ``chip_smoke.py`` is.

Everything that touches the topology happens inside fixtures and tests
of THIS file: only one process may load libtpu, xdist gives a file to
one worker, and a module that loaded it at import would leave the
other workers with nothing to collect. Each case asserts what it
compiled: off-TPU ``interpret=None`` lowers the INTERPRETED kernel,
which compiles fine and proves nothing, so every case either passes
``interpret=False`` or steers ``_interpret_default``, and looks for the
Mosaic custom call in the compiled text.
"""

from __future__ import annotations

import os
import sys
from functools import partial

import numpy as np
import pytest

# the chip_smoke.py widths (tools/lm_mfu.py flagship LM, engine defaults)
_LM = dict(vocab_size=256, d_model=768, n_heads=12, n_layers=12, d_ff=3072,
           max_seq=2048)
_SLOTS, _MAX_PROMPT, _MAX_NEW, _BLOCK, _CHUNK = 32, 1024, 64, 16, 32
_KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    """The described topology, with the persistent compile cache off
    while this file's tests run (an entry compiled for a described chip
    cannot be read back without one, and the next compile would warn)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Callers that leave ``interpret=None`` (the LM's ``_attention``,
    ``ring_attention``) ask ``_interpret_default``; here the backend is
    the CPU, so steer it to the answer the chip gives. The submodule is
    reached through ``sys.modules``: ``ops.flash_attention`` the
    attribute is the re-exported function."""
    import multiverso_tpu.ops  # noqa: F401  (registers the submodule)

    module = sys.modules["multiverso_tpu.ops.flash_attention"]
    monkeypatch.setattr(module, "_interpret_default", lambda: False)


def _compile(fn, *args, **jit_kwargs):
    import jax

    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count(_KERNEL)


def _qkv(sharding, seq, heads, dim, dtype, batch=None):
    import jax

    shape = (seq, heads, dim) if batch is None else (batch, seq, heads * dim)
    return (jax.ShapeDtypeStruct(shape, dtype, sharding=sharding),) * 3


# (seq, heads, head_dim, dtype): the LM-step shape first, then the
# ragged / small cases tools/tpu_validate.py checks numerically
_FLASH_SHAPES = [
    (2048, 12, 64, "bfloat16"),
    (256, 4, 64, "float32"),
    (512, 8, 128, "float32"),
    (1024, 2, 128, "float32"),
    (384, 4, 64, "float32"),
]


@pytest.mark.parametrize("seq,heads,dim,dtype", _FLASH_SHAPES)
def test_flash_attention_forward_compiles(one_chip, seq, heads, dim, dtype):
    from multiverso_tpu.ops import flash_attention

    compiled = _compile(
        partial(flash_attention, causal=True, interpret=False),
        *_qkv(one_chip, seq, heads, dim, np.dtype(dtype)))
    assert _kernel_calls(compiled) >= 1


@pytest.mark.parametrize("seq,heads,dim,dtype", _FLASH_SHAPES)
def test_flash_attention_backward_compiles(one_chip, seq, heads, dim, dtype):
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        *_qkv(one_chip, seq, heads, dim, np.dtype(dtype)))
    # forward + the backward kernels (one fused, or dq and dk/dv)
    assert _kernel_calls(compiled) >= 2


def test_lm_attention_call_compiles(one_chip, compiled_kernels):
    """The call exactly as ``models.transformer._attention`` makes it at
    the chip_smoke LM step: vmapped over batch 4, ``attention="flash"``
    crossing over to the kernel at seq 2048."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import _attention

    heads = _LM["n_heads"]
    args = _qkv(one_chip, _LM["max_seq"], heads, _LM["d_model"] // heads,
                jnp.bfloat16, batch=4)
    fwd = _compile(lambda q, k, v: _attention(q, k, v, heads, "flash"),
                   *args)
    assert _kernel_calls(fwd) >= 1

    def loss(q, k, v):
        return jnp.sum(_attention(q, k, v, heads, "flash")
                       .astype(jnp.float32) ** 2)

    bwd = _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert _kernel_calls(bwd) >= 2


def test_lm_attention_below_crossover_is_xla(one_chip, compiled_kernels):
    """The other side of the dispatch: below ``FLASH_CROSSOVER_SEQ`` the
    same call holds no kernel — so the assertion above means something."""
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import _attention

    heads = _LM["n_heads"]
    args = _qkv(one_chip, 1024, heads, _LM["d_model"] // heads, jnp.bfloat16,
                batch=4)
    compiled = _compile(lambda q, k, v: _attention(q, k, v, heads, "flash"),
                        *args)
    assert _kernel_calls(compiled) == 0


def test_ring_attention_pallas_compiles_on_four_chips(topo,
                                                      compiled_kernels):
    """``flash_attention_partial`` as ``ring_attention(impl="pallas")``
    calls it (traced block offsets, default ``interpret``), the sequence
    sharded over all four described chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from multiverso_tpu.ops.ring_attention import ring_attention
    from multiverso_tpu.topology import SEQ_AXIS

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (SEQ_AXIS,))
    seq_sharded = NamedSharding(mesh, P(SEQ_AXIS, None, None))
    args = (jax.ShapeDtypeStruct((4096, 12, 64), jnp.bfloat16,
                                 sharding=seq_sharded),) * 3
    compiled = _compile(
        lambda q, k, v: ring_attention(q, k, v, mesh, causal=True,
                                       impl="pallas"), *args)
    text = compiled.as_text()
    assert _KERNEL in text
    assert "collective-permute" in text


# -- the decode engine's paged programs at the serving phase's shapes ----------
def _paged_shapes(one_chip, slots, per_slot, **lm):
    """(cfg, params, k_pool, v_pool, block_tables) as ShapeDtypeStructs on
    one described chip: ``per_slot`` blocks of ``_BLOCK`` a slot, a
    contiguous-equivalent pool plus the scratch block."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   init_params)

    cfg = TransformerConfig(dtype=jnp.bfloat16, **dict(_LM, **lm))
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(lambda: init_params(cfg)))
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, slots * per_slot + 1, _BLOCK, cfg.d_model),
        cfg.dtype, sharding=one_chip)
    return cfg, params, pool, pool, _ints(one_chip, slots, per_slot)


@pytest.fixture(scope="module")
def serving_shapes(one_chip):
    """Sized as ``register_decoder(slots=32, max_prompt=1024,
    max_new=64)`` sizes them: T = 1088, 68 blocks of 16 per slot."""
    return _paged_shapes(one_chip, _SLOTS,
                         -(-(_MAX_PROMPT + _MAX_NEW) // _BLOCK))


def _ints(sharding, *shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _fits_hbm(compiled, budget=16 * 2 ** 30) -> bool:
    mem = compiled.memory_analysis()
    # donated pools alias their outputs: count them once
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return held < budget


def _compile_step(one_chip, shapes, t_logical, kernel=True):
    """``decode_step_paged`` as the engine jits it: pools donated, and
    (``kernel``) attention by ``paged_mq_attention`` COMPILED, the
    choice ``make_serving_programs`` makes on the chip for these
    bfloat16 pools of 16-row blocks (here the backend is the CPU, so the
    test makes it)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import decode_step_paged
    from multiverso_tpu.ops.paged_attention import paged_mq_attention

    cfg, params, kc, vc, bt = shapes
    slots = bt.shape[0]
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    attend = partial(paged_mq_attention, interpret=False) if kernel else None
    return _compile(
        lambda p, kc, vc, bt, tok, pos, act: decode_step_paged(
            cfg, p, kc, vc, bt, tok, pos, act, t_logical=t_logical,
            paged_attention=attend),
        params, kc, vc, bt, _ints(one_chip, slots), _ints(one_chip, slots),
        active, donate_argnums=(1, 2))


def _compile_chunk(one_chip, shapes, t_logical, chunk):
    """``prefill_chunk_paged`` as the engine jits it: pools donated."""
    from multiverso_tpu.models.transformer import prefill_chunk_paged

    cfg, params, kc, vc, bt = shapes
    return _compile(
        lambda p, kc, vc, bt, slot, toks, off, n: prefill_chunk_paged(
            cfg, p, kc, vc, bt, slot, toks, off, n, t_logical=t_logical),
        params, kc, vc, bt, _ints(one_chip), _ints(one_chip, chunk),
        _ints(one_chip), _ints(one_chip), donate_argnums=(1, 2))


def _pools_aliased(compiled, pool) -> bool:
    # the engine donates both pools on the chip: the compiler must alias
    # them, or every token step copies the whole cache
    return compiled.memory_analysis().alias_size_in_bytes >= 2 * np.prod(
        pool.shape) * 2


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "view"])
def test_decode_step_paged_compiles(one_chip, serving_shapes, kernel):
    """Both forms of the step: the kernel (bfloat16 pools on the chip)
    and the gathered view (every other dtype, block shape and backend)."""
    compiled = _compile_step(one_chip, serving_shapes,
                             _MAX_PROMPT + _MAX_NEW, kernel)
    assert (_kernel_calls(compiled) > 0) == kernel
    assert _fits_hbm(compiled)
    assert _pools_aliased(compiled, serving_shapes[2])


def test_prefill_chunk_paged_compiles(one_chip, serving_shapes):
    compiled = _compile_chunk(one_chip, serving_shapes,
                              _MAX_PROMPT + _MAX_NEW, _CHUNK)
    assert _fits_hbm(compiled)
    assert _pools_aliased(compiled, serving_shapes[2])


# the serving cell's shapes (benchmarks/traffic/closed128.json on
# benchmarks/configs/gpt2-small.json): 128 slots of 64 blocks of 16, the
# 50,257-wide head, 512-token chunks, T = 896 + 128
_CELL = dict(slots=128, per_slot=64, vocab_size=50257, max_seq=1024)
_CELL_T, _CELL_CHUNK = 1024, 512
# what must not come back: a float32 copy of the slots x T view (the
# one-token products were no matrix products to the compiler) and a copy
# of one layer's whole pool (``pool[i]`` of a pool just scattered into)
_CELL_FORBIDDEN = ("= f32[128,64,16,768]", "= f32[128,1024,768]",
                   "= f32[128,1024,12,64]", "= bf16[8193,16,768]")
# nor, in the step, the view itself: the kernel reads the live blocks out
# of the pool (24 gathers of 201 MB a step before)
_CELL_VIEW = ("= bf16[8192,16,768]", "= bf16[128,1024,768]")


@pytest.mark.parametrize("program,temp_mb", [("step", 100), ("chunk", 50)])
def test_serving_cell_programs_move_kv_once(one_chip, program, temp_mb):
    """``decode_step_paged`` and ``prefill_chunk_paged`` at the CELL's
    shapes. The step gathers NO view: one ``paged_mq_attention`` call a
    layer over the pools as they lie (650 MB of temporaries with the
    view, 1,230 MB before PR 26). The chunk moves one slot's K and V
    from the pool to its products once, in bfloat16."""
    shapes = _paged_shapes(one_chip, **_CELL)
    if program == "step":
        compiled = _compile_step(one_chip, shapes, _CELL_T)
        forbidden = _CELL_FORBIDDEN + _CELL_VIEW
        assert _kernel_calls(compiled) == shapes[0].n_layers
    else:
        compiled = _compile_chunk(one_chip, shapes, _CELL_T, _CELL_CHUNK)
        forbidden = _CELL_FORBIDDEN
    text = compiled.as_text()
    for array in forbidden:
        assert array not in text, array
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb * 10 ** 6
    assert _pools_aliased(compiled, shapes[2])
    assert _fits_hbm(compiled)


def test_verify_step_paged_compiles(one_chip, serving_shapes):
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import verify_step_paged

    cfg, params, kc, vc, bt = serving_shapes
    window = 5                                  # spec_k = 4
    active = jax.ShapeDtypeStruct((_SLOTS,), jnp.bool_, sharding=one_chip)
    compiled = _compile(
        lambda p, kc, vc, bt, toks, pos, act, nv: verify_step_paged(
            cfg, p, kc, vc, bt, toks, pos, act, nv,
            t_logical=_MAX_PROMPT + _MAX_NEW),
        params, kc, vc, bt, _ints(one_chip, _SLOTS, window),
        _ints(one_chip, _SLOTS), active, _ints(one_chip, _SLOTS),
        donate_argnums=(1, 2))
    assert _fits_hbm(compiled)


def test_cow_block_copy_compiles(one_chip, serving_shapes):
    from multiverso_tpu.models.transformer import cow_block_copy

    _, _, kc, vc, _ = serving_shapes
    compiled = _compile(cow_block_copy, kc, vc, _ints(one_chip),
                        _ints(one_chip), donate_argnums=(0, 1))
    mem = compiled.memory_analysis()
    # in place: a copy-on-write of one block must not copy the pools
    assert mem.alias_size_in_bytes >= 2 * np.prod(kc.shape) * 2
    assert mem.temp_size_in_bytes < np.prod(kc.shape) * 2


def test_sharded_decode_programs_compile_on_two_chips(topo, serving_shapes):
    """The ``decode_tp=2`` engine's pre-partitioned step and chunk
    programs (``chip_smoke.py --chips 4`` serves through them): pools
    head-sharded, so each chip holds half the cache, and the two
    all-reduces per layer are the collectives the compiler puts in."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from multiverso_tpu.models.transformer import (
        DECODE_TP_AXIS, make_sharded_decode_programs)

    cfg, params, kc, _, bt = serving_shapes
    mesh = Mesh(np.asarray(topo.devices[:2]), (DECODE_TP_AXIS,))
    progs = make_sharded_decode_programs(
        cfg, mesh, _MAX_PROMPT + _MAX_NEW, donate=True)
    rep = NamedSharding(mesh, P())
    place = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    params = jax.tree.map(place, params, progs["param_shardings"])
    pool = place(kc, progs["pool_sharding"])
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=rep)
    active = jax.ShapeDtypeStruct((_SLOTS,), jnp.bool_, sharding=rep)
    step = progs["step"].lower(params, pool, pool, place(bt, rep),
                               ints(_SLOTS), ints(_SLOTS), active).compile()
    chunk = progs["chunk"].lower(params, pool, pool, place(bt, rep), ints(),
                                 ints(_CHUNK), ints(), ints()).compile()
    pool_bytes = int(np.prod(kc.shape)) * 2
    for compiled in (step, chunk):
        assert "all-reduce" in compiled.as_text()
        mem = compiled.memory_analysis()      # per device
        assert mem.alias_size_in_bytes >= pool_bytes      # 2 pools / 2 chips
        assert mem.argument_size_in_bytes < 2 * pool_bytes


# -- the latent-pool models: both programs at their cells' shapes ----------------
def _latent_cell_programs(one_chip, monkeypatch, module, model, config,
                          traffic):
    """``serving_programs`` of a latent-pool model at its cell's shapes,
    no weights drawn: ``(cfg, t, progs, args of step, args of chunk)``
    with every array a shape on the described chip. The step takes the
    kernel, as on the chip (``serving_programs`` asks the backend, which
    is the CPU here: the test answers for it)."""
    import json

    import jax
    import jax.numpy as jnp

    from multiverso_tpu.serving.programs import EngineSpec

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", config)) as fh:
        cfg = module.config_from_dict(json.load(fh), 1)
    with open(os.path.join(root, "benchmarks", "traffic", traffic)) as fh:
        t = json.load(fh)
    S, Bs, C = t["slots"], 16, t["prefill_token_budget"]
    T = t["max_prompt"] + t["max_new"]
    M = -(-T // Bs)
    import multiverso_tpu.ops  # noqa: F401  (registers the submodule)

    monkeypatch.setattr(sys.modules["multiverso_tpu.ops.paged_attention"],
                        "_on_tpu", lambda: True)
    lm = object.__new__(model)                  # no weights drawn
    lm.config = cfg
    progs = lm.serving_programs(EngineSpec(
        name="cell", slots=S, max_prompt=t["max_prompt"],
        max_new=t["max_new"], cache_len=T, block_size=Bs, blocks_per_seq=M,
        pool_blocks=S * M, budget=C, prefix=True, tp=1, mesh=None,
        kv_quant="none", param_quant="none", spec_k=0, prefill_sp="none",
        donate=True))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place,
                          jax.eval_shape(lambda: module.init_params(cfg)))
    (pshape, pdtype), (cshape, cdtype) = progs.pools
    assert pshape == (cfg.n_sublayers, S * M + 1, Bs, 640)
    pool = jax.ShapeDtypeStruct(pshape, pdtype, sharding=one_chip)
    counters = jax.ShapeDtypeStruct(cshape, cdtype, sharding=one_chip)
    bt = _ints(one_chip, S, M)
    step = (params, pool, counters, bt, _ints(one_chip, S),
            _ints(one_chip, S),
            jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip))
    chunk = (params, pool, counters, bt, _ints(one_chip), _ints(one_chip, C),
             _ints(one_chip), _ints(one_chip))
    return cfg, t, progs, step, chunk


@pytest.mark.parametrize("cell,program,temp_mb", [
    ("longcat", "step", 150), ("longcat", "chunk", 850),
    ("dotsvlm1", "step", 150), ("dotsvlm1", "chunk", 850)])
def test_latent_cell_programs_fit_the_chip(one_chip, monkeypatch, cell,
                                           program, temp_mb):
    """The two programs of ``serve_longcat_ep32_closed128``
    (``longcat-flash-ep32.json`` under ``closed128_gen768.json``: 10.4 GB
    of weights, a 3.0 GB pool) and of ``serve_dotsvlm1_ep16_closed128``
    (``dots-vlm1-ep16.json`` under ``closed128_p512_gen512.json``: 11.0
    GB of weights, a 1.0 GB pool, 128 heads): weights and pool are
    arguments, the pool is aliased (a 576-wide row made the compiler
    copy the pool whole around every scatter: the row is padded to 640),
    no copy of the pool and no float32 copy of the ``slots x T`` view
    appear, and arguments plus temporaries stay inside the chip. The
    step runs one ``paged_mq_attention`` a sublayer and gathers no
    ``[slots, T, 640]`` view (LongCat: 8 gathers of 377 MB a step
    before)."""
    from multiverso_tpu.models import deepseek_v3, longcat

    module, model, config, traffic, min_args = {
        "longcat": (longcat, longcat.LongCatLM, "longcat-flash-ep32.json",
                    "closed128_gen768.json", 13.3e9),
        "dotsvlm1": (deepseek_v3, deepseek_v3.DeepSeekV3LM,
                     "dots-vlm1-ep16.json", "closed128_p512_gen512.json",
                     12.0e9)}[cell]
    cfg, t, progs, step, chunk = _latent_cell_programs(
        one_chip, monkeypatch, module, model, config, traffic)
    compiled = (progs.step.lower(*step) if program == "step"
                else progs.chunk.lower(*chunk)).compile()
    pshape = progs.pools[0][0]
    S, T, Bs = t["slots"], t["max_prompt"] + t["max_new"], pshape[2]
    mem = compiled.memory_analysis()
    pool_bytes = int(np.prod(pshape)) * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < temp_mb * 10 ** 6
    assert mem.argument_size_in_bytes > min_args       # weights + pool
    assert _fits_hbm(compiled, budget=15.75 * 2 ** 30)
    text = compiled.as_text()
    assert f"copy(bf16[{pshape[0]},{pshape[1]},{Bs},640]" not in text
    assert f"f32[{S},{T},640]" not in text
    if program == "step":
        assert _kernel_calls(compiled) == cfg.n_sublayers
        for view in (f"= bf16[{pshape[1] - 1},{Bs},640]",
                     f"= bf16[{S},{T},640]"):
            assert view not in text, view


@pytest.mark.parametrize("program,temp_mb", [("step", 150), ("chunk", 850)])
def test_ling_cell_programs_fit_the_chip(one_chip, monkeypatch, program,
                                         temp_mb):
    """The two programs of ``serve_ling3_ep4_closed128``
    (``ling3-flash-ep4.json`` under ``closed128_p1024_gen768.json``: 10.5
    GB of weights, 1.61 GB of float32 KDA states a slot, 57 MB of conv
    tails, a 0.29 GB latent pool): weights and the three pools are
    arguments, every pool is aliased (a copy of the states is 1.6 GB a
    step), no copy of the state pool or of one layer's slab of it
    appears, temporaries are bounded, arguments plus temporaries stay
    inside the chip, and the step runs one ``paged_mq_attention`` for its
    one latent layer and one ``kda_step_pool`` for each of its six KDA
    layers, with no layer's slab of the state pool ever cut out."""
    import json

    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import ling
    from multiverso_tpu.serving.programs import EngineSpec

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "ling3-flash-ep4.json")) as fh:
        cfg = ling.config_from_dict(json.load(fh), 1)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "closed128_p1024_gen768.json")) as fh:
        t = json.load(fh)
    S, Bs, C = t["slots"], 16, t["prefill_token_budget"]
    T = t["max_prompt"] + t["max_new"]
    M = -(-T // Bs)
    import multiverso_tpu.ops  # noqa: F401  (registers the submodule)

    # serving_programs asks the backend, the CPU here: the test answers
    # for it, for both kernels of the step
    for module in ("paged_attention", "kda"):
        monkeypatch.setattr(sys.modules[f"multiverso_tpu.ops.{module}"],
                            "_on_tpu", lambda: True)
    lm = object.__new__(ling.LingLM)            # no weights drawn
    lm.config = cfg
    progs = lm.serving_programs(EngineSpec(
        name="cell", slots=S, max_prompt=t["max_prompt"],
        max_new=t["max_new"], cache_len=T, block_size=Bs, blocks_per_seq=M,
        pool_blocks=S * M, budget=C, prefix=False, tp=1, mesh=None,
        kv_quant="none", param_quant="none", spec_k=0, prefill_sp="none",
        donate=True))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place,
                          jax.eval_shape(lambda: ling.init_params(cfg)))
    shapes = [shape for shape, _ in progs.pools]
    assert shapes[:3] == [(1, S * M + 1, Bs, 640), (6, S, 32, 128, 128),
                          (6, S, 3, 12288)]
    assert progs.bytes_per_slot == 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    pools = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in progs.pools]
    bt = _ints(one_chip, S, M)
    if program == "step":
        args = (params, *pools, bt, _ints(one_chip, S), _ints(one_chip, S),
                jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip))
        compiled = progs.step.lower(*args).compile()
    else:
        args = (params, *pools, bt, _ints(one_chip), _ints(one_chip, C),
                _ints(one_chip), _ints(one_chip))
        compiled = progs.chunk.lower(*args).compile()
    mem = compiled.memory_analysis()
    donated = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
                  for shape, dtype in progs.pools[:3])
    assert donated > 1.9e9 and mem.alias_size_in_bytes >= donated
    assert mem.temp_size_in_bytes < temp_mb * 10 ** 6
    assert mem.argument_size_in_bytes > 12.4e9      # weights + pools
    assert _fits_hbm(compiled, budget=15.75 * 2 ** 30)
    text = compiled.as_text()
    for copied in (f"copy(f32[6,{S},32,128,128]", f"copy(f32[{S},32,128,128]",
                   f"copy(bf16[1,{S * M + 1},{Bs},640]"):
        assert copied not in text, copied
    if program == "step":
        # one paged_mq_attention for the latent layer, one kda_step_pool
        # a KDA layer: each state read once and written once, in place
        assert _kernel_calls(compiled) == 1 + cfg.n_kda_layers == 7
        assert f"f32[{S},32,128,128]" not in text   # no slab of the pool


_COLLECTIVES = ("all-reduce(", "all-gather(", "all-to-all(",
                "collective-permute(", "reduce-scatter(")


@pytest.mark.parametrize("workers,servers,temp_gb", [
    (1, 1, 5.0), (2, 2, 4.2)])
def test_w2v_cell_step_writes_rows_without_a_dense_delta(
        topo, monkeypatch, workers, servers, temp_gb):
    """The fused step of ``w2v_news3m_sgns`` and of ``w2v_news3m_dp2x2``
    (``w2v-news3m-d300.json``: two ``bf16[3000000, 300]`` tables, 65,536
    pairs a worker and step, 25 steps a dispatch).

    The program is still ``jit_fused``; no float32 copy or delta of a
    table (or of a server's half) exists; the candidates are packed by
    ONE 163,840-key sort; the tables are written by three plain
    scatter-adds (centres, contexts, negatives), told nothing about
    their ids (``indices_are_sorted`` makes XLA:TPU sweep the whole
    table, docs/W2V_KERNEL.md); temporaries stay under the bound (all
    but ~0.3 GB of them are the two tables in the row-major 384-column
    layout the loop carries, converted at the program's edges). On the
    (2, 2) mesh the scan holds ONE collective, the all-reduce over the
    server axis of the rows each server gathered from its half."""
    import json
    import threading

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from multiverso_tpu.apps.wordembedding import subsample_probs
    from multiverso_tpu.models.word2vec import Word2Vec, Word2VecConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "w2v-news3m-d300.json")) as fh:
        cfg = json.load(fh)
    V, D = cfg["vocab_size"], cfg["embedding_size"]
    mesh = Mesh(np.array(topo.devices[:workers * servers]).reshape(
        workers, servers), ("worker", "server"))

    class Table:            # what the trainer reads of a table
        def __init__(self):
            self.mesh, self.num_row, self.padded_shape = mesh, V, (V, D)
            self.sharding = NamedSharding(mesh, P("server", None))
            self._lock, self.version = threading.Lock(), 0

    # the zipf law of benchmarks/gen.py's w2v_counts, in its own words
    r = np.arange(1, V + 1, dtype=np.float64)
    counts = cfg["total_words"] * np.log1p(1.0 / r) / np.log(V + 1.0)
    # nothing can be put on a described device: the trainer's key stays
    # where it was made
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    model = Word2Vec(Word2VecConfig(
        vocab_size=V, embedding_size=D, window=cfg["window"],
        negative=cfg["negative"], init_lr=cfg["init_lr"],
        batch_size=cfg["batch_size_per_worker"] * workers,
        oversample=cfg["oversample"], neg_pool_size=cfg["neg_pool_size"],
        row_mean_updates=cfg["row_mean_updates"],
        row_mean_static=cfg["row_mean_static"],
        row_update_cap=cfg["row_update_cap"],
        shared_negatives=cfg["shared_negatives"],
        seed=cfg["trainer_seed"]), Table(), Table(), counts=counts)
    monkeypatch.undo()
    assert model._dp_local() == workers
    model._build_static_scales(subsample_probs(counts, cfg["sample"]))
    n, W = cfg["corpus_words"], cfg["window"]
    M = model._candidate_batch(n)
    fused = model._build_corpus_step(cfg["steps_per_dispatch"], M)

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    table = arg((V, D), jnp.bfloat16, P("server", None))
    ext = n + M + 2 * W
    compiled = fused.lower(
        table, table, None, None, arg((ext,), jnp.int32),
        arg((ext,), jnp.int32), arg((ext,), jnp.float32),
        arg((), jnp.float32), arg((2,), jnp.uint32),
        arg((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_fused")
    shard = V // servers
    for dense in (f"f32[{V},{D}]", f"f32[{shard},{D}]"):
        assert dense not in text, dense
    packs = [ln for ln in text.splitlines()
             if " sort(" in ln and f"s32[{M // workers}]" in ln]
    assert len(packs) == 1, packs
    writes = [ln for ln in text.splitlines()
              if f"= bf16[{shard},{D}]" in ln and " scatter(" in ln]
    assert len(writes) == 3, writes     # centres, contexts, negatives
    assert not any("indices_are_sorted=true" in ln
                   or "unique_indices=true" in ln for ln in writes)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * shard * D * 2      # donated
    assert mem.temp_size_in_bytes < temp_gb * 1e9, mem.temp_size_in_bytes
    assert _fits_hbm(compiled, budget=15.75 * 2 ** 30)
    in_scan = [ln for ln in text.splitlines()
               if "/while/body/" in ln and any(c in ln for c in _COLLECTIVES)]
    if workers * servers == 1:
        assert not in_scan
    else:
        assert len(in_scan) == 1 and "all-reduce(" in in_scan[0], in_scan
        for gathered in (f"bf16[{cfg['batch_size_per_worker']},{D}]",
                         f"bf16[1024,{cfg['negative']},{D}]"):
            assert gathered in in_scan[0].split(" all-reduce(")[0]
