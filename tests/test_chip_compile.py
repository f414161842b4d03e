"""The fused cascade compiles for a TPU v5e chip at qwen1.5-0.5b width.

Interpret mode accepts layouts Mosaic refuses (scalar VMEM stores,
blocks off the (8, 128) tiling, VMEM/SMEM overflow), so these tests lower
the real kernel for a *described* v5e chip — no chip needed — on
`jax.ShapeDtypeStruct` operands at the serving shape: the 153,600-row
padded vocabulary x 1,024 table, K = 4, B = 8 lanes sharing one column
list or with one each (and the single-query entry point); round 1 runs
in the kernel's dense phase.  The topology is described inside a
fixture, never at import: only one process may load the TPU compiler
library at a time.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.boundedme_jax import make_plan
from repro.core.schedule import cert_coeffs, flatten_schedule
from repro.kernels.fused_cascade import (_vmem_bytes,
                                         fused_cascade_batched_pallas,
                                         fused_cascade_pallas,
                                         pull_group_size)

K, LANES = 4, 8


def _kernel_eqn(fn, *args, **kw):
    """The one `pallas_call` equation that ``fn(*args, **kw)`` traces."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", None)
                sub = getattr(sub, "jaxpr", sub)
                if sub is not None and (hit := find(sub)) is not None:
                    return hit
        return None

    return find(jax.make_jaxpr(lambda a, k: fn(*a, **k))(args, kw).jaxpr)


def _assert_ring(eqn, flat, *, G, R, Cs, lanes, G1, engaged=True):
    """The grid is the dense phase's ``G1`` steps plus each query's
    grouped steps, and VMEM holds a (2, G, R, Cs) pull ring; ``engaged``:
    rounds 2+ pull, in fewer steps than one tile a step."""
    first, size = flat.groups(G)
    assert eqn.params["grid_mapping"].grid == (G1 + lanes * len(first),)
    shapes = [v.aval.shape for v in eqn.params["jaxpr"].invars]
    assert (2, G, R, Cs) in shapes
    if engaged:
        assert size.max() > 1 and len(first) < flat.n_steps
    else:      # no pulls after round 1: the grid is the per-step list's
        assert size.max() == 0 and len(first) == flat.n_steps
    return int((size > 0).sum())


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(one_chip, *, precision, adaptive, batched, track_var=False,
             per_query_cols=False, arch="qwen1.5-0.5b", pull_mode="row"):
    cfg = get_config(arch)
    n, d = cfg.padded_vocab, cfg.d_model
    plan = make_plan(n, d, K=K, eps=0.1, delta=0.1, value_range=0.2,
                     block=min(512, d), precision=precision,
                     pull_mode=pull_mode)
    flat = flatten_schedule(plan.schedule, final_coverage=(
        precision == "fp32" and not adaptive))
    assert flat.t_dense > 0          # round 1 runs in the dense phase
    slotcode, rmeta = flat.packed()
    R, C, nt, nb = plan.tile, plan.block, plan.n_tiles, plan.n_blocks

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    vdt = jnp.float32 if precision == "fp32" else jnp.int8
    q_shape = (LANES, nb, C) if batched else (nb, C)
    args = [sds((nt, nb, R, C), vdt), sds(q_shape, vdt),
            sds(rmeta.shape, jnp.int32),
            sds(((LANES,) if per_query_cols else ()) + flat.col_pos.shape,
                jnp.int32)]
    extra = {}
    if precision == "int8":
        extra["vscale"] = sds((nt, nb), jnp.float32)
        extra["qscale"] = sds(q_shape[:-1], jnp.float32)
    if adaptive:
        extra["cert"] = sds(cert_coeffs(plan.schedule).shape, jnp.float32)
    fn = fused_cascade_batched_pallas if batched else fused_cascade_pallas

    def run(V4, q, rmeta, cols, **e):   # the schedule is host data
        return fn(V4, q, slotcode, rmeta, cols, **e, n_arms=plan.n, K=K,
                  t_final=flat.t_final, n_final=flat.n_final,
                  t_dense=flat.t_dense, n_valid=cfg.vocab, k_cert=K,
                  track_var=track_var, interpret=False)

    # one column list for the batch: one dense step scores every lane,
    # about 1 MiB of tiles (64 of (8, 512), 256 of (8, 128))
    dense = -(-nt // (2**20 // _vmem_bytes((R, C), vdt))) * flat.t_dense
    lanes = LANES if batched else 1
    _assert_ring(_kernel_eqn(run, *args, **extra), flat,
                 G=pull_group_size(R, C, vdt), R=R, Cs=C, lanes=lanes,
                 G1=dense * (lanes if per_query_cols else 1),
                 engaged=pull_mode == "coord")
    return jax.jit(run).lower(*args, **extra).compile()


@pytest.mark.parametrize("mode", ["fixed", "adaptive", "bernstein"])
@pytest.mark.parametrize("precision", ["fp32", "int8"])
@pytest.mark.parametrize("batched", [True, False], ids=["B8", "B1"])
def test_fused_cascade_compiles_for_v5e(one_chip, batched, precision, mode):
    """'bernstein' adds the M2 accumulator of the variance-aware radii."""
    compiled = _compile(one_chip, precision=precision,
                        adaptive=mode != "fixed", batched=batched,
                        track_var=mode == "bernstein")
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["fixed", "adaptive", "bernstein"])
@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_per_query_columns_compile_for_v5e(one_chip, precision, mode):
    """A column list per query: each dense round-1 step scores one query,
    B times the dense steps of the shared list."""
    compiled = _compile(one_chip, precision=precision,
                        adaptive=mode != "fixed", batched=True,
                        track_var=mode == "bernstein", per_query_cols=True)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_coord_mode_compiles_for_v5e(one_chip, precision):
    """128-wide coordinate tiles at tinyllama-1.1b width (32,768 x 2,048):
    the dense phase sums them with `f32_tile_dot` itself."""
    compiled = _compile(one_chip, precision=precision, adaptive=False,
                        batched=True, arch="tinyllama-1.1b",
                        pull_mode="coord")
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tier", ["int4", "pq"])
def test_tiers_without_mosaic_lowering_refuse_loudly(tier):
    """int4 and pq have no Mosaic lowering: they must raise, never fall
    back to interpret mode or the jnp path."""
    V4 = jnp.zeros((2, 1, 8, 64 if tier == "int4" else 16), jnp.int8)
    kw = dict(n_arms=16, K=1, t_final=1, n_final=1, t_dense=0,
              interpret=False)
    if tier == "int4":
        kw.update(vscale=jnp.ones((2, 1)), qscale=jnp.ones((1,)),
                  packed_int4=True)
        qb = jnp.zeros((1, 128), jnp.int8)
    else:
        V4 = V4.astype(jnp.uint8)
        kw.update(codebook=jnp.zeros((1, 16, 16, 8), jnp.float32))
        qb = jnp.zeros((1, 128), jnp.float32)
    code = jnp.asarray(np.zeros(1, np.int32))
    with pytest.raises(NotImplementedError, match=tier):
        fused_cascade_pallas(V4, qb, code, jnp.zeros((1, 3), jnp.int32),
                             code, **kw)


def test_deepseek_v3_bf16_head_compiles_for_v5e(one_chip):
    """DeepSeek-V3's whole head as the benchmark serves it: 129,280 x
    7,168 stored in bf16 (16,160 tiles, 14 blocks), K = 1, a verify batch
    of 16 lanes sharing one column list; round 1 in the dense phase, then
    1,608 grid steps a query that pull groups of up to 16 tiles."""
    n, d, lanes = 129_280, 7_168, 16
    plan = make_plan(n, d, K=1, eps=0.1, delta=0.1, value_range=0.24,
                     block=512)
    flat = flatten_schedule(plan.schedule, final_coverage=True)
    assert (plan.n_tiles, plan.n_blocks, flat.t_dense) == (16_160, 14, 2)
    slotcode, rmeta = flat.packed()
    R, C = plan.tile, plan.block

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(V4, Qb, rmeta, cols):
        return fused_cascade_batched_pallas(
            V4, Qb, slotcode, rmeta, cols, n_arms=plan.n, K=1,
            t_final=flat.t_final, n_final=flat.n_final,
            t_dense=flat.t_dense, n_valid=n, interpret=False)

    args = (sds((plan.n_tiles, plan.n_blocks, R, C), jnp.bfloat16),
            sds((lanes, plan.n_blocks, C), jnp.float32),
            sds(rmeta.shape, jnp.int32), sds(flat.col_pos.shape, jnp.int32))
    G = pull_group_size(R, C, jnp.bfloat16)
    assert G == 16                        # a bf16 tile takes 16 KiB of VMEM
    assert _assert_ring(_kernel_eqn(run, *args), flat, G=G, R=R, Cs=C,
                        lanes=lanes, G1=253 * 2) == 1_608
    compiled = jax.jit(run).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cell", ["cmdr35b-shard4", "cmdr35b-vp4-shard",
                                  "whisper-medium"])
def test_benchmark_cells_compile_for_v5e(one_chip, cell):
    """Each fp32 cell's kernel at its own shape, 8 lanes sharing one
    column list: the Command-R shard (64,000 x 8,192) pulls 831 groups a
    query after round 1; a shard of the 4-chip head runs its plan at
    delta / 4; Whisper (53,248 x 1,024, K = 5) pulls nothing after round
    1, so its grid keeps one step per scheduled step."""
    from repro.distributed.sharding import make_shard_plan

    kw = dict(K=1, eps=0.1, delta=0.1, value_range=0.24, tile=8, block=512)
    n_valid = 64_000
    if cell == "cmdr35b-vp4-shard":
        plan = make_shard_plan(256_000, 8_192, 4, **kw)[0]
    elif cell == "cmdr35b-shard4":
        plan = make_plan(64_000, 8_192, **kw)
    else:
        kw.update(K=5)
        plan = make_plan(53_248, 1_024, **kw)
        n_valid = 51_865
    flat = flatten_schedule(plan.schedule, final_coverage=True)
    slotcode, rmeta = flat.packed()
    R, C, nt, nb = plan.tile, plan.block, plan.n_tiles, plan.n_blocks

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(V4, Qb, rmeta, cols):
        return fused_cascade_batched_pallas(
            V4, Qb, slotcode, rmeta, cols, n_arms=plan.n, K=plan.K,
            t_final=flat.t_final, n_final=flat.n_final,
            t_dense=flat.t_dense, n_valid=n_valid, interpret=False)

    args = (sds((nt, nb, R, C), jnp.float32),
            sds((LANES, nb, C), jnp.float32),
            sds(rmeta.shape, jnp.int32), sds(flat.col_pos.shape, jnp.int32))
    groups = _assert_ring(_kernel_eqn(run, *args), flat, G=16, R=R, Cs=C,
                          lanes=LANES, G1=-(-nt // 64) * flat.t_dense,
                          engaged=cell != "whisper-medium")
    assert groups == {"cmdr35b-shard4": 831, "cmdr35b-vp4-shard": 1_053,
                      "whisper-medium": 0}[cell]
    compiled = jax.jit(run).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
