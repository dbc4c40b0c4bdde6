"""End-to-end PS/Hybrid training through the Executor against a live local
PS cluster (reference: examples/ctr run with --comm PS/Hybrid, SURVEY §2.5).

The embedding table lives on the parameter server; each step the executor
pulls the batch's rows, runs the jitted XLA step, and pushes row gradients.
"""
import numpy as np

from test_ps import run_cluster

NROWS = 40
WIDTH = 8
SLOTS = 4
BATCH = 16


def _build_model(ht):
    embed = ht.init.random_normal((NROWS, WIDTH), stddev=0.1, name="embed",
                                  is_embed=True)
    idx = ht.Variable(name="idx", trainable=False)
    y_ = ht.Variable(name="y_", trainable=False)
    vec = ht.embedding_lookup_op(embed, idx)            # (B, SLOTS, WIDTH)
    flat = ht.array_reshape_op(vec, (-1, SLOTS * WIDTH))
    w = ht.init.xavier_uniform((SLOTS * WIDTH, 1), name="w")
    prob = ht.sigmoid_op(ht.matmul_op(flat, w))
    loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0])
    return embed, idx, y_, loss, prob


def _gen_batch(rng):
    bidx = rng.randint(0, NROWS, (BATCH, SLOTS)).astype(np.float32)
    # label = majority of slots drawn from the upper half of the id range:
    # learnable as a per-row score summed across slots (unlike parity)
    by = ((bidx >= NROWS // 2).sum(axis=1) > SLOTS // 2)
    by = by.reshape(BATCH, 1).astype(np.float32)
    return bidx, by


def _hybrid_training(client, rank, tmpdir):
    import hetu_tpu as ht
    embed, idx, y_, loss, prob = _build_model(ht)
    opt = ht.optim.SGDOptimizer(0.1)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op], "validate": [loss, prob]},
                     ctx=ht.cpu(0), comm_mode="Hybrid")
    rng = np.random.RandomState(7 + rank)
    losses = []
    # success is bounded by STEPS (a fixed 200-step budget with a fixed
    # convergence margin), not by wall time — the harness timeout exists
    # only to catch hangs, so a slow host cannot flip the verdict
    # (at 120 steps this seed's margin is ~0.020, right on the bound)
    for _ in range(200):
        bidx, by = _gen_batch(rng)
        out = ex.run("train", feed_dict={idx: bidx, y_: by})
        losses.append(float(out[0].asnumpy()))
    client.BarrierWorker()
    np.save(f"{tmpdir}/hybrid_losses_{rank}.npy", np.asarray(losses))
    # learning happened (embedding rows + dense weights both moved)
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02, (
        np.mean(losses[:10]), np.mean(losses[-10:]))
    # validate subexecutor shares the PS tables
    bidx, by = _gen_batch(rng)
    vloss = float(ex.run("validate", feed_dict={idx: bidx, y_: by})[0].asnumpy())
    assert np.isfinite(vloss)


def _ps_mode_dense(client, rank, tmpdir):
    # comm_mode='PS': dense params live on the server too (DDPushPull path)
    import hetu_tpu as ht
    x = ht.Variable(name="x", trainable=False)
    y_ = ht.Variable(name="y_", trainable=False)
    w = ht.init.random_normal((4, 2), stddev=0.5, name="w")
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
    opt = ht.optim.SGDOptimizer(0.2)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), comm_mode="PS")
    rng = np.random.RandomState(3 + rank)
    true_w = np.array([[2.0, -1.0], [-1.0, 2.0], [0.5, 0.5], [1.0, -2.0]],
                      np.float32)
    losses = []
    for _ in range(50):
        bx = rng.randn(BATCH, 4).astype(np.float32)
        logits = bx @ true_w
        by = np.eye(2, dtype=np.float32)[logits.argmax(1)]
        out = ex.run("train", feed_dict={x: bx, y_: by})
        losses.append(float(out[0].asnumpy()))
    client.BarrierWorker()
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.05
    # both workers see the same server-resident weights
    value = ex.fetch_dense_parameter_value([w])[0].asnumpy()
    np.save(f"{tmpdir}/w_{rank}.npy", value)
    client.BarrierWorker()


def _hybrid_with_cache(client, rank, tmpdir):
    import hetu_tpu as ht
    embed, idx, y_, loss, prob = _build_model(ht)
    opt = ht.optim.SGDOptimizer(0.1)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                     comm_mode="Hybrid", cstable_policy="LFUOpt",
                     cache_bound=2)
    rng = np.random.RandomState(11 + rank)
    losses = []
    # steps-bounded like _hybrid_training; this seed's margin at 150
    # steps measured ~0.12-0.13 — 6x the 0.02 bound, so the shorter
    # budget still decides deterministically despite bounded staleness
    for _ in range(150):
        bidx, by = _gen_batch(rng)
        out = ex.run("train", feed_dict={idx: bidx, y_: by})
        losses.append(float(out[0].asnumpy()))
    client.BarrierWorker()
    np.save(f"{tmpdir}/cache_losses_{rank}.npy", np.asarray(losses))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02, (
        np.mean(losses[:10]), np.mean(losses[-10:]))


def _ps_checkpoint(client, rank, tmpdir):
    import hetu_tpu as ht
    embed, idx, y_, loss, prob = _build_model(ht)
    opt = ht.optim.SGDOptimizer(0.1)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                     comm_mode="Hybrid")
    rng = np.random.RandomState(5)
    for _ in range(5):
        bidx, by = _gen_batch(rng)
        ex.run("train", feed_dict={idx: bidx, y_: by})
    client.BarrierWorker()
    ckpt = f"{tmpdir}/ckpt"
    ex.save(ckpt)
    before = ex.ps_runtime.pull_sparse_rows(
        ex.ps_runtime.params[id(embed)], np.arange(NROWS))
    for _ in range(3):
        bidx, by = _gen_batch(rng)
        ex.run("train", feed_dict={idx: bidx, y_: by})
    client.BarrierWorker()
    ex.load(ckpt)
    after = ex.ps_runtime.pull_sparse_rows(
        ex.ps_runtime.params[id(embed)], np.arange(NROWS))
    np.testing.assert_allclose(after, before, rtol=1e-6)


def _make_loader_model(ht, steps, seed, batch=BATCH):
    """Dataloader-fed embedding model (prefetch needs peekable batches)."""
    rng = np.random.RandomState(seed)
    bidx, by = [], []
    for _ in range(steps):
        bi, b = _gen_batch(rng)
        bidx.append(bi)
        by.append(b)
    bidx = np.concatenate(bidx)
    by = np.concatenate(by)
    embed = ht.init.random_normal((NROWS, WIDTH), stddev=0.1, name="embed",
                                  is_embed=True)
    idx = ht.dataloader_op([ht.Dataloader(bidx, batch, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(by, batch, "train")])
    vec = ht.embedding_lookup_op(embed, idx)
    flat = ht.array_reshape_op(vec, (-1, SLOTS * WIDTH))
    w = ht.init.xavier_uniform((SLOTS * WIDTH, 1), name="w")
    prob = ht.sigmoid_op(ht.matmul_op(flat, w))
    loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0])
    train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return loss, train_op


def _prefetch_overlap(client, rank, tmpdir):
    """prefetch=True (default): after the first step every pull is a
    prefetch hit issued while the previous step ran; pushes are async.

    The counts are EVENT-counted and exact, not statistical: issuance
    happens on the run() thread after every step, and consumption
    (``take_prefetched``) BLOCKS on the in-flight future — a slow host
    makes the hit slower, never a miss. Overlap is a performance
    property; the ledger proves the issuance/consumption pairing."""
    import hetu_tpu as ht
    steps = 40
    loss, train_op = _make_loader_model(ht, steps, seed=13 + rank)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                     comm_mode="Hybrid")
    losses = [float(ex.run("train")[0].asnumpy()) for _ in range(steps)]
    perf = ex.ps_runtime.perf
    # step 0 pulls synchronously; every later step consumes the prefetch
    # issued by its predecessor; the last issue is never consumed
    assert perf["prefetch_issued"] == steps, perf
    assert perf["prefetch_hits"] == steps - 1, perf
    assert perf["prefetch_misses"] == 0, perf
    assert perf["sync_pulls"] == 1, perf
    ex.ps_runtime.drain()
    assert perf["async_pushes"] == steps, perf
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]), (
        np.mean(losses[:10]), np.mean(losses[-10:]))
    client.BarrierWorker()


def _bsp_prefetch_losses(client, rank, tmpdir, prefetch):
    """BSP + single worker: prefetch rides the push stream (push -> barrier ->
    pull ordering), so training is bit-identical to the synchronous path."""
    import hetu_tpu as ht
    steps = 30
    loss, train_op = _make_loader_model(ht, steps, seed=21)
    ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0), seed=0,
                     comm_mode="Hybrid", bsp=True, prefetch=prefetch)
    losses = [float(ex.run("train")[0].asnumpy()) for _ in range(steps)]
    np.save(f"{tmpdir}/bsp_losses_{int(bool(prefetch))}.npy",
            np.asarray(losses))
    if prefetch:
        ex.ps_runtime.drain()
        assert ex.ps_runtime.perf["prefetch_hits"] >= steps - 2, \
            ex.ps_runtime.perf
    client.BarrierWorker()


def _bsp_prefetch_on(client, rank, tmpdir):
    _bsp_prefetch_losses(client, rank, tmpdir, prefetch=True)


def _bsp_prefetch_off(client, rank, tmpdir):
    _bsp_prefetch_losses(client, rank, tmpdir, prefetch=False)


def _shared_table_two_lookups(client, rank, tmpdir):
    """One PS table feeding TWO lookup ops (shared CTR embedding) must train
    identically to the single-lookup refactoring (lookup on the concatenated
    index sets) — the reference accumulates such grads as IndexedSlices
    (optimizer.py:64-82). Momentum runs server-side, so this also proves the
    host-side dedup-sum: the optimizer state must advance once per row per
    step regardless of how many lookups/slots referenced the row.

    Both executors run BSP (`bsp=True`, as `_server_opt_schedule_sparse`
    does): the pull stream is then the push stream, so step N+1's rows are
    pulled after step N's push has landed, in both. Under ASP the two
    streams race and a pull may or may not see the last push (staleness of
    up to a step, by design), so two ASP runs agree step by step only when
    the machine is idle: under six test workers step 11 read 0.670046
    against 0.668759 (ISSUE 42)."""
    import os
    import hetu_tpu as ht
    S1, S2 = 2, 3
    rng0 = np.random.RandomState(11)
    table0 = rng0.randn(NROWS, WIDTH).astype(np.float32) * 0.1
    w0 = rng0.randn((S1 + S2) * WIDTH, 1).astype(np.float32) * 0.3

    def build(shared):
        embed = ht.Variable(name="embed", value=table0.copy(), is_embed=True)
        y_ = ht.Variable(name="y_", trainable=False)
        if shared:
            i1 = ht.Variable(name="i1", trainable=False)
            i2 = ht.Variable(name="i2", trainable=False)
            v1 = ht.embedding_lookup_op(embed, i1)      # (B, S1, W)
            v2 = ht.embedding_lookup_op(embed, i2)      # (B, S2, W)
            flat = ht.concat_op(
                ht.array_reshape_op(v1, (-1, S1 * WIDTH)),
                ht.array_reshape_op(v2, (-1, S2 * WIDTH)), axis=1)
            feeds = (i1, i2)
        else:
            ic = ht.Variable(name="ic", trainable=False)
            vec = ht.embedding_lookup_op(embed, ic)     # (B, S1+S2, W)
            flat = ht.array_reshape_op(vec, (-1, (S1 + S2) * WIDTH))
            feeds = (ic,)
        w = ht.Variable(name="w", value=w0.copy())
        prob = ht.sigmoid_op(ht.matmul_op(flat, w))
        loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0])
        opt = ht.optim.MomentumOptimizer(0.1, momentum=0.9)
        train_op = opt.minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         comm_mode="Hybrid", bsp=True)
        return ex, feeds, y_, embed

    os.environ["HETU_PS_ID_BASE"] = "0"
    exA, feedsA, yA, embA = build(shared=True)
    os.environ["HETU_PS_ID_BASE"] = "100"
    exB, feedsB, yB, embB = build(shared=False)

    rng = np.random.RandomState(7)
    for step in range(12):
        # duplicate rows across (and within) the two index sets on purpose
        i1 = rng.randint(0, NROWS, (BATCH, S1)).astype(np.float32)
        i2 = rng.randint(0, NROWS, (BATCH, S2)).astype(np.float32)
        by = (rng.rand(BATCH, 1) > 0.5).astype(np.float32)
        la = exA.run("train", feed_dict={feedsA[0]: i1, feedsA[1]: i2,
                                         yA: by})[0].asnumpy()
        lb = exB.run("train", feed_dict={
            feedsB[0]: np.concatenate([i1, i2], axis=1), yB: by})[0].asnumpy()
        np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6,
                                   err_msg=f"step {step}")
    pA = exA.ps_runtime.params[id(embA)]
    pB = exB.ps_runtime.params[id(embB)]
    rows = np.arange(NROWS)
    ta = exA.ps_runtime.pull_sparse_rows(pA, rows)
    tb = exB.ps_runtime.pull_sparse_rows(pB, rows)
    np.testing.assert_allclose(ta, tb, rtol=1e-5, atol=1e-6)
    assert not np.allclose(ta, table0)  # the table actually trained

    # cross-target: the same table ALSO feeds a validate head through its
    # own lookup node. Only the train-graph lookup may become a gradient
    # target (the validate lookup stages rows but never pushes grads).
    os.environ["HETU_PS_ID_BASE"] = "200"
    embed = ht.Variable(name="embed2", value=table0.copy(), is_embed=True)
    it = ht.Variable(name="it", trainable=False)
    iv = ht.Variable(name="iv", trainable=False)
    y2 = ht.Variable(name="y2", trainable=False)
    wt = ht.Variable(name="wt", value=w0[:S1 * WIDTH].copy())
    flat_t = ht.array_reshape_op(ht.embedding_lookup_op(embed, it),
                                 (-1, S1 * WIDTH))
    prob_t = ht.sigmoid_op(ht.matmul_op(flat_t, wt))
    loss_t = ht.reduce_mean_op(ht.binarycrossentropy_op(prob_t, y2), [0])
    train2 = ht.optim.MomentumOptimizer(0.1, momentum=0.9).minimize(loss_t)
    flat_v = ht.array_reshape_op(ht.embedding_lookup_op(embed, iv),
                                 (-1, S1 * WIDTH))
    prob_v = ht.sigmoid_op(ht.matmul_op(flat_v, wt))
    ex2 = ht.Executor({"train": [loss_t, train2], "validate": [prob_v]},
                      ctx=ht.cpu(0), comm_mode="Hybrid")
    for _ in range(3):
        i1 = rng.randint(0, NROWS, (BATCH, S1)).astype(np.float32)
        by = (rng.rand(BATCH, 1) > 0.5).astype(np.float32)
        l2 = ex2.run("train", feed_dict={it: i1, y2: by})[0].asnumpy()
        assert np.isfinite(l2)
    pv = ex2.run("validate", feed_dict={
        iv: rng.randint(0, NROWS, (BATCH, S1)).astype(np.float32)})[0].asnumpy()
    assert np.all(np.isfinite(pv))


def test_shared_table_two_lookups(tmp_path):
    run_cluster(_shared_table_two_lookups, tmp_path, n_workers=1, timeout=300)


def _server_opt_schedule_sparse(client, rank, tmpdir):
    """Momentum + StepScheduler on a PS-hosted embedding must match the
    device-resident oracle exactly: the per-step lr rides the push opts
    (SetPushOpts -> store.h UpdateOpts), so the schedule is no longer frozen
    at init (reference: server applies whatever lr arrives with the push,
    ps-lite optimizer.h:15-75). Every row is touched every step so device
    (dense momentum) and server (pushed-rows-only momentum) agree."""
    import hetu_tpu as ht
    SLOTS_ = 4
    B = NROWS // SLOTS_
    rng0 = np.random.RandomState(21)
    table0 = rng0.randn(NROWS, WIDTH).astype(np.float32) * 0.1
    w0 = rng0.randn(SLOTS_ * WIDTH, 1).astype(np.float32) * 0.3

    def build(comm_mode, **kw):
        embed = ht.Variable(name="embed", value=table0.copy(), is_embed=True)
        idx = ht.Variable(name="idx", trainable=False)
        y_ = ht.Variable(name="y_", trainable=False)
        vec = ht.embedding_lookup_op(embed, idx)
        flat = ht.array_reshape_op(vec, (-1, SLOTS_ * WIDTH))
        w = ht.Variable(name="w", value=w0.copy())
        prob = ht.sigmoid_op(ht.matmul_op(flat, w))
        loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0])
        opt = ht.optim.MomentumOptimizer(
            ht.lr.StepScheduler(0.2, step_size=3, gamma=0.5), momentum=0.9)
        train_op = opt.minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         comm_mode=comm_mode, **kw)
        return ex, embed, idx, y_

    import os
    os.environ["HETU_PS_ID_BASE"] = "300"
    exP, embP, idxP, yP = build("Hybrid", bsp=True)
    exD, embD, idxD, yD = build(None)

    rng = np.random.RandomState(5)
    for step in range(8):
        bidx = rng.permutation(NROWS).reshape(B, SLOTS_).astype(np.float32)
        by = (rng.rand(B, 1) > 0.5).astype(np.float32)
        lp = exP.run("train", feed_dict={idxP: bidx, yP: by})[0].asnumpy()
        ld = exD.run("train", feed_dict={idxD: bidx, yD: by})[0].asnumpy()
        np.testing.assert_allclose(lp, ld, rtol=1e-5, atol=1e-6,
                                   err_msg=f"step {step}")
    pP = exP.ps_runtime.params[id(embP)]
    served = exP.ps_runtime.pull_sparse_rows(pP, np.arange(NROWS))
    device = np.asarray(exD.state["params"][id(embD)])
    np.testing.assert_allclose(served, device, rtol=1e-4, atol=1e-5)
    assert not np.allclose(served, table0)


def _server_opt_l2_wd_dense(client, rank, tmpdir):
    """comm_mode='PS' dense params with (a) Adam + l2reg + schedule and
    (b) AdamW + decoupled weight decay must match device oracles: l2reg and
    weight_decay ride the push opts and apply against the CURRENT server
    value under the param lock."""
    import os
    import hetu_tpu as ht
    rng0 = np.random.RandomState(31)
    w0 = rng0.randn(6, 3).astype(np.float32) * 0.5

    def build(opt, comm_mode, base):
        os.environ["HETU_PS_ID_BASE"] = str(base)
        x = ht.Variable(name="x", trainable=False)
        y_ = ht.Variable(name="y_", trainable=False)
        w = ht.Variable(name="w", value=w0.copy())
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
        train_op = opt.minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         comm_mode=comm_mode)
        return ex, x, y_, w

    cases = [
        ("adam+l2reg+schedule",
         lambda: ht.optim.AdamOptimizer(
             ht.lr.StepScheduler(0.05, step_size=3, gamma=0.5), l2reg=0.02)),
        ("adamw+wd",
         lambda: ht.optim.AdamWOptimizer(0.05, weight_decay=0.1)),
    ]
    rng = np.random.RandomState(9)
    for i, (label, mk) in enumerate(cases):
        exP, xP, yP, wP = build(mk(), "PS", 400 + 10 * i)
        exD, xD, yD, wD = build(mk(), None, 400 + 10 * i + 5)
        for step in range(8):
            bx = rng.randn(BATCH, 6).astype(np.float32)
            by = np.eye(3, dtype=np.float32)[rng.randint(0, 3, BATCH)]
            lp = exP.run("train", feed_dict={xP: bx, yP: by})[0].asnumpy()
            ld = exD.run("train", feed_dict={xD: bx, yD: by})[0].asnumpy()
            np.testing.assert_allclose(
                lp, ld, rtol=1e-5, atol=1e-6, err_msg=f"{label} step {step}")
        served = exP.ps_runtime.pull_dense_value(
            exP.ps_runtime.params[id(wP)])
        device = np.asarray(exD.state["params"][id(wD)])
        np.testing.assert_allclose(served, device, rtol=1e-4, atol=1e-5,
                                   err_msg=label)


def _shared_table_union_prefetch(client, rank, tmpdir):
    """A shared table with dataloader-fed lookups prefetches the UNION of
    the peeked next batches: after step 0 every pre-step pull is a hit, and
    under BSP the losses match the prefetch-off run exactly."""
    import hetu_tpu as ht
    S1, S2, steps = 2, 3, 12
    rng0 = np.random.RandomState(17)
    i1 = rng0.randint(0, NROWS, (steps * BATCH, S1)).astype(np.float32)
    i2 = rng0.randint(0, NROWS, (steps * BATCH, S2)).astype(np.float32)
    by = (rng0.rand(steps * BATCH, 1) > 0.5).astype(np.float32)
    table0 = rng0.randn(NROWS, WIDTH).astype(np.float32) * 0.1
    w0 = rng0.randn((S1 + S2) * WIDTH, 1).astype(np.float32) * 0.3

    import os

    def run(prefetch, base):
        os.environ["HETU_PS_ID_BASE"] = str(base)
        embed = ht.Variable(name="embed", value=table0.copy(), is_embed=True)
        d1 = ht.dataloader_op([ht.Dataloader(i1, BATCH, "train")])
        d2 = ht.dataloader_op([ht.Dataloader(i2, BATCH, "train")])
        dy = ht.dataloader_op([ht.Dataloader(by, BATCH, "train")])
        v1 = ht.embedding_lookup_op(embed, d1)
        v2 = ht.embedding_lookup_op(embed, d2)
        flat = ht.concat_op(
            ht.array_reshape_op(v1, (-1, S1 * WIDTH)),
            ht.array_reshape_op(v2, (-1, S2 * WIDTH)), axis=1)
        w = ht.Variable(name="w", value=w0.copy())
        prob = ht.sigmoid_op(ht.matmul_op(flat, w))
        loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, dy), [0])
        train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
        ex = ht.Executor({"train": [loss, train_op]}, ctx=ht.cpu(0),
                         comm_mode="Hybrid", bsp=True, prefetch=prefetch)
        losses = [float(ex.run("train")[0].asnumpy()) for _ in range(steps)]
        perf = dict(ex.ps_runtime.perf)
        ex.ps_runtime.drain()
        return losses, perf

    on_losses, on_perf = run(True, 500)
    off_losses, _ = run(False, 600)
    np.testing.assert_allclose(on_losses, off_losses, rtol=1e-6, atol=1e-7)
    # union prefetch engaged: after the first step every pull hits
    assert on_perf["prefetch_hits"] >= steps - 1, on_perf
    assert on_perf["prefetch_misses"] == 0, on_perf


def test_shared_table_union_prefetch(tmp_path):
    run_cluster(_shared_table_union_prefetch, tmp_path, n_workers=1,
                timeout=300)


def test_server_opt_schedule_sparse(tmp_path):
    run_cluster(_server_opt_schedule_sparse, tmp_path, n_workers=1,
                timeout=300)


def test_server_opt_l2_wd_dense(tmp_path):
    run_cluster(_server_opt_l2_wd_dense, tmp_path, n_workers=1, timeout=300)


def test_prefetch_overlap(tmp_path):
    run_cluster(_prefetch_overlap, tmp_path, n_workers=1, timeout=300)


def test_bsp_prefetch_exact(tmp_path):
    run_cluster(_bsp_prefetch_on, tmp_path, n_workers=1, timeout=300)
    run_cluster(_bsp_prefetch_off, tmp_path, n_workers=1, timeout=300)
    a = np.load(f"{tmp_path}/bsp_losses_1.npy")
    b = np.load(f"{tmp_path}/bsp_losses_0.npy")
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_hybrid_training(tmp_path):
    # 900s is a hang bound, not a pacing bound: the 200-step body takes
    # ~1-4 min even on a loaded 1-2 core host
    run_cluster(_hybrid_training, tmp_path, n_workers=2, timeout=900)


def test_ps_mode_dense_training(tmp_path):
    run_cluster(_ps_mode_dense, tmp_path, n_workers=2, timeout=300)
    a = np.load(f"{tmp_path}/w_0.npy")
    b = np.load(f"{tmp_path}/w_1.npy")
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_hybrid_training_with_cache(tmp_path):
    run_cluster(_hybrid_with_cache, tmp_path, n_workers=2, timeout=900)


def test_ps_checkpoint_save_load(tmp_path):
    run_cluster(_ps_checkpoint, tmp_path, n_workers=1, timeout=300)
