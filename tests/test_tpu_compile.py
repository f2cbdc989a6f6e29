"""The main path compiled for one described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot: a block shape Mosaic refuses, a kernel over its VMEM budget, a
training step that does not fit the chip's HBM.  The topology is
described inside a fixture — only the test worker that runs this file
loads the TPU library.  JAX's persistent compilation cache is off around
the compiles (a TPU executable cannot be read back without the chip).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: HBM one v5e chip gives a program (16 GB part)
V5E_HBM_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _tick_operands(dev, B, P, d, m, k_max, masked, churn, adaptive):
    """Shape-only operands of one sweep tick, as the sweep planner feeds
    them (rows padded to a multiple of 16)."""
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    state = dict(steps=S((B, P), i32), alive=S((B, P), b),
                 computing=S((B, P), b), event_time=S((B, P), f32),
                 ready=S((B, P), f32), blocked=S((B, P), b),
                 pend_leave=S((B,), i32), pend_join=S((B,), i32),
                 w=S((B, d), f32), pulled=S((B, P, d), f32))
    rand = dict(X=S((P, m, d), f32), dur=S((B, P), f32), mb=S((P, m), f32))
    if k_max == 1 and not masked:
        rand["u1"] = S((P,), f32)
    elif k_max > 0:
        rand["scores"] = S((B, P, P) if masked else (P, P), f32)
    if churn:
        rand["leave"], rand["join"] = S((B, P), f32), S((B, P), f32)
    params = dict(
        beta_clip=S((B,), i32), compute_time=S((B, P), f32),
        dist_hops=S((B,), i32), eps=S((), f32), full_view=S((B,), b),
        horizon=S((B,), f32), is_asp=S((B,), b), lr=S((B,), f32),
        noise_std=S((B,), f32), poll=S((), f32), sampled=S((B,), b),
        staleness=S((B,), i32), valid_slot=S((B, P), b),
        w_true=S((B, d), f32))
    if adaptive:
        state.update(pol_thr=S((B,), i32), pol_ema=S((B, P), f32),
                     pol_beta=S((B,), i32))
        params.update(is_dssp=S((B,), b), is_ebsp=S((B,), b),
                      is_anneal=S((B,), b), pol_lo=S((B,), i32),
                      beta_lo=S((B,), i32), ebsp_range=S((B,), f32),
                      ebsp_alpha=S((B,), f32))
    return state, rand, params, S((), f32), S((B,), i32), S((B,), i32)


@pytest.mark.parametrize("B,P,d,k_max,masked,churn", [
    (48, 100, 32, 1, False, False),      # Fig-2 matrix: β = 1 fast path
    (48, 100, 32, 3, False, False),      # Fig-2 size, shared-score rank test
    (48, 100, 32, 2, True, True),        # Fig-2 size under churn (masked)
    (16, 1000, 1000, 10, False, False),  # paper scale: β = 1% of 1000
], ids=["fig2-beta1", "fig2-rank", "fig2-churn", "paper-beta10"])
def test_tick_kernel_compiles(one_chip, B, P, d, k_max, masked, churn):
    from repro.kernels.psp_tick import psp_tick_tpu
    fn = jax.jit(functools.partial(psp_tick_tpu, k_max=k_max,
                                   has_churn=churn, masked=masked,
                                   adaptive=True))
    compiled = fn.lower(*_tick_operands(one_chip, B, P, d, 8, k_max, masked,
                                        churn, True)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_psp_train_step_fits_one_chip(one_chip):
    """The donated full-width qwen2-0.5b PSP tick at the smoke's size
    (W = 2 workers, batch 2, seq 512) fits one chip's HBM, with the
    carried state updated in place."""
    from repro.configs import get_config
    from repro.core.spmd_psp import PSPConfig, psp_init
    from repro.launch.train import make_psp_step
    from repro.models import init_model
    from repro.optim import adamw, warmup_cosine
    W, batch, seq = 2, 2, 512
    cfg = get_config("qwen2-0.5b")
    opt = adamw(warmup_cosine(3e-3, 1, 3))
    pcfg = PSPConfig(barrier="pbsp", n_workers=W, sample_size=2,
                     staleness=3, straggler_frac=0.25)
    state = jax.eval_shape(
        lambda k: psp_init(pcfg, init_model(cfg, k), opt.init,
                           jax.random.fold_in(k, 1)),
        jax.random.PRNGKey(0))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), state)
    toks = jax.ShapeDtypeStruct((W, batch, seq), jnp.int32,
                                sharding=one_chip)
    m = make_psp_step(cfg, pcfg, opt).lower(state, toks).compile() \
        .memory_analysis()
    assert m.alias_size_in_bytes >= 0.99 * m.argument_size_in_bytes
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert peak < V5E_HBM_BYTES, f"{peak / 2 ** 30:.2f} GiB"
