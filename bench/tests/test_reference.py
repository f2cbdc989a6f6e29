"""The plain qwen2 reference against the trainer it checks, at a size the
CPU holds: the same weights from the seed, the same loss and gradient in
float32, and a replay of a cell's checked ticks that agrees with what the
trainer's session saw."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import run
import tiny
import traffic

ref = run.module("configs", "qwen2-0.5b.ref.py")
drv = run.module("drivers", "train.py")

PBSP, SYNC = "train.qwen2-0.5b.pbsp", "train.qwen2-0.5b.sync"


def _paths(tree):
    return {"/".join(str(k.key) for k in p): v
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def test_init_matches_the_trainer_to_rounding():
    from repro.models import init_model
    _, cfg = tiny.cell(PBSP)
    key = jax.random.PRNGKey(traffic.seed32(2 ** 31 + 5))
    got = _paths(jax.jit(functools.partial(
        init_model, drv.model_config(cfg)))(key))
    want = ref.init_params(cfg, key)
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-9,
                                   err_msg=k)


def test_loss_and_grad_match_the_trainer_in_float32():
    from repro.models import loss_fn
    _, cfg = tiny.cell(PBSP)
    mc = drv.model_config(cfg)
    params = ref.init_params(cfg, jax.random.PRNGKey(3))
    params = {k: v + 0.01 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
              for i, (k, v) in enumerate(sorted(params.items()))}
    toks = traffic.tokens(3, 0, (2, 32), 512, {"kind": "successor",
                                              "follow_p": 0.5})
    nested = {}
    for k, v in params.items():
        node = nested
        *head, last = k.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            nested, {"tokens": jnp.asarray(toks)}, mc)
    want_loss, want_g = ref.loss_and_grad(params, toks, cfg, block=1)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    g = _paths(g)
    for k in want_g:
        np.testing.assert_allclose(g[k], want_g[k], rtol=2e-3, atol=1e-6,
                                   err_msg=k)


@pytest.mark.parametrize("name", [PBSP, SYNC])
def test_replay_follows_the_session(name):
    wl, cfg = tiny.cell(name)
    seen = drv.setup(wl, cfg, 7).seen
    want = ref.replay(cfg, wl, 7)
    r = compare.train_readings(wl, seen, want)
    assert r["loss_gap"] < 1e-6
    assert r["grad_norm_gap"] < 1e-5
    assert r["grad_sample_gap"] < 1e-5
    assert r["delta_norm_gap"] < 1e-4
    if name == PBSP:
        assert r["barrier_mismatch"] == 0
        assert sum(want["pushes"]) >= 1


def test_reference_refuses_a_sample_it_cannot_draw():
    wl, cfg = tiny.cell(PBSP)
    with pytest.raises(ValueError):
        ref.Clock(dict(wl, workers=4, sample_size=1))


def test_checked_ticks_reach_the_stragglers_push_and_the_release():
    """The pBSP cell's checked ticks cover a whole barrier cycle: the
    straggler's first push, the blocked fast worker's release, and the
    fast worker's next push from the view it then pulled."""
    wl, _ = run.cell(PBSP)
    clock = ref.Clock(wl)
    slow = int(np.flatnonzero(clock.slow)[0])
    fast = 1 - slow
    events = []
    for tick in range(wl["check_ticks"]):
        push = clock.pushes()
        allowed = clock.advance(push)
        events += [(tick, "push", w) for w in np.flatnonzero(push)]
        events += [(tick, "start", w) for w in np.flatnonzero(allowed)]
    straggler = min(t for t, e, w in events if e == "push" and w == slow)
    release = min(t for t, e, w in events
                  if e == "start" and w == fast and t >= straggler)
    assert any(e == "push" and w == fast and t > release
               for t, e, w in events)
    # the fast worker polls while it is blocked
    assert release - straggler >= 1
    assert straggler > 3


def test_loss_only_is_the_loss_of_loss_and_grad():
    _, cfg = tiny.cell(PBSP)
    params = ref.init_params(cfg, jax.random.PRNGKey(5))
    toks = traffic.tokens(5, 0, (2, 32), 512, {"kind": "successor",
                                              "follow_p": 0.5})
    want, _ = ref.loss_and_grad(params, toks, cfg, block=1)
    assert ref.loss_only(params, toks, cfg, block=1) == pytest.approx(
        want, rel=1e-6)


@pytest.mark.parametrize("change", [{"qkv_bias": False},
                                    {"mlp_type": "geglu"},
                                    {"tie_word_embeddings": False}])
def test_reference_refuses_an_architecture_it_does_not_implement(change):
    _, cfg = tiny.cell(PBSP)
    with pytest.raises(ValueError):
        ref.param_layout(dict(cfg, **change))
