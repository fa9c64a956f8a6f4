"""AdamW, global-norm clipping and the warmup + cosine LR schedule that the
surrogate cost model trains with."""
import jax
import jax.numpy as jnp

from repro.core import optimizer as opt


def test_adamw_decreases_quadratic():
    params = {"w": jnp.array([3.0, -2.0, 1.0])}
    state = opt.init(params)
    cfg = opt.OptConfig(lr=0.1, warmup_steps=0, total_steps=100, weight_decay=0.0)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    l0 = loss(params)
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, state, _ = opt.apply(cfg, params, g, state)
    assert loss(params) < l0 * 0.05


def test_grad_clipping():
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    cfg = opt.OptConfig(lr=1e-3, clip_norm=1.0, warmup_steps=0)
    g = {"w": jnp.array([100.0, 0.0, 0.0])}
    _, _, metrics = opt.apply(cfg, params, g, state)
    assert metrics["grad_norm"] > 99


def test_lr_schedule_shape():
    cfg = opt.OptConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(opt.lr_at(cfg, jnp.int32(s))) for s in [0, 5, 10, 55, 100]]
    assert lrs[0] < lrs[1] < lrs[2]            # warmup
    assert lrs[2] >= lrs[3] >= lrs[4]          # cosine decay
    assert abs(lrs[4] - 0.1) < 1e-5            # floor
