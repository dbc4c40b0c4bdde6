"""Pure-JAX ResNet-18 training twin — the A/B competitor the reference keeps
in-repo for its own benchmarks (``examples/cnn/{tf_main,torch_main}.py``,
``run_tf_horovod.py``): the same model and step, written directly against
jax with no framework, so the graph-API executor's overhead is measurable
as (twin samples/s) / (executor samples/s).

Run: ``python jax_twin.py [--batch-size 256] [--dtype bf16]``
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def make_resnet18(cdtype):
    """Returns (init_params, loss_fn) matching models/ResNet.py's
    architecture (basic blocks 2-2-2-2, BN, global pool) in NCHW."""

    def conv(x, w, stride, pad):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def bn(x, scale, bias):
        m = jnp.mean(x, (0, 2, 3), keepdims=True)
        v = jnp.var(x, (0, 2, 3), keepdims=True)
        shp = (1, -1, 1, 1)
        return ((x - m) * jax.lax.rsqrt(v + 1e-2) * scale.reshape(shp)
                + bias.reshape(shp))

    def init_params(key):
        params = []

        def add_conv(key, cin, cout, k):
            w = jax.random.normal(key, (cout, cin, k, k), jnp.float32) \
                * np.sqrt(2.0 / (cin * k * k))
            params.append((w, jnp.ones(cout), jnp.zeros(cout)))

        keys = iter(jax.random.split(key, 64))
        add_conv(next(keys), 3, 64, 3)
        cur = 64
        for (nb, outc, stride) in zip((2, 2, 2, 2), (64, 128, 256, 512),
                                      (1, 2, 2, 2)):
            for b in range(nb):
                s = stride if b == 0 else 1
                add_conv(next(keys), cur, outc, 3)
                add_conv(next(keys), outc, outc, 3)
                if s != 1 or cur != outc:
                    add_conv(next(keys), cur, outc, 1)
                cur = outc
        wfc = jax.random.normal(next(keys), (512, 10), jnp.float32) * 0.05
        params.append((wfc, jnp.zeros(10)))
        return params

    def apply(params, x):
        x = x.astype(cdtype)
        it = iter(params[:-1])

        def cbr(x, stride, relu=True):
            w, s, b = next(it)
            k = w.shape[2]
            out = conv(x, w.astype(cdtype), stride, k // 2)
            out = bn(out, s.astype(cdtype), b.astype(cdtype))
            return jax.nn.relu(out) if relu else out

        x = cbr(x, 1)
        cur = 64
        for (nb, outc, stride) in zip((2, 2, 2, 2), (64, 128, 256, 512),
                                      (1, 2, 2, 2)):
            for b in range(nb):
                s = stride if b == 0 else 1
                h = cbr(x, s)
                h = cbr(h, 1, relu=False)
                if s != 1 or cur != outc:
                    x = cbr(x, s, relu=False)
                x = jax.nn.relu(h + x)
                cur = outc
        x = jnp.mean(x, (2, 3))
        wfc, bfc = params[-1]
        return (x @ wfc.astype(cdtype) + bfc.astype(cdtype)).astype(
            jnp.float32)

    def loss_fn(params, x, y):
        logp = jax.nn.log_softmax(apply(params, x))
        return -jnp.mean(jnp.sum(y * logp, axis=1))

    return init_params, loss_fn


def bench(batch_size=256, dtype="bf16", iters=30, warmup=5, lr=0.1,
          momentum=0.9):
    cdtype = jnp.bfloat16 if dtype in ("bf16", "bfloat16") else jnp.float32
    init_params, loss_fn = make_resnet18(cdtype)
    params = init_params(jax.random.PRNGKey(0))
    mom = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(params, mom, x, y):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        g = jax.tree.map(lambda v: v.astype(jnp.float32), g)
        mom = jax.tree.map(lambda m, gv: momentum * m + gv, mom, g)
        params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
        return loss, params, mom

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch_size, 3, 32, 32), jnp.float32)
    y = jnp.asarray(np.eye(10)[rng.randint(0, 10, batch_size)], jnp.float32)
    for _ in range(warmup):
        loss, params, mom = step(params, mom, x, y)
    float(np.asarray(loss))  # host read: the warm-up is done on the device
    t0 = time.time()
    for _ in range(iters):
        loss, params, mom = step(params, mom, x, y)
    float(np.asarray(loss))
    dt = (time.time() - t0) / iters
    return batch_size / dt, dt * 1000


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--dtype", default="bf16", choices=["f32", "bf16"])
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()
    sps, ms = bench(args.batch_size, args.dtype, args.iters)
    print(f"jax twin resnet18 bs={args.batch_size} {args.dtype}: "
          f"{sps:,.1f} samples/s  {ms:.2f} ms/step")


if __name__ == "__main__":
    main()
