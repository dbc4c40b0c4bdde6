"""qwen3-next-80b-a3b as a user's job script builds it: the published
config.json (cut to one chip's share, config.json `reduced`) through
`hf_qwen3_next.config_from_hf`, weights from the program's own initialiser,
`transformer.make_train_step` (next-token loss on the untied head plus the
routers' balance loss at the published weight, AdamW in the step). Only
architecture, shapes, optimizer and compute dtype are stated; the chunked
rule's form (`kda.scan`'s own rule), attention implementation, fused
cross-entropy, recomputation, the grouped matmul and kernel mode stay the
program's defaults.
"""
import time

import numpy as np

# The check holds the TIMED program: ONE more call of the job's own compiled
# step (`self._step`, the program the window timed, at its 16,384 tokens) on
# the correctness sample (one sequence, another stream of the same seed, the
# weights and AdamW state the window left). What that call returns is what
# is compared: its loss, the gradient it applied (AdamW's first moment is m'
# = b1 m + (1 - b1) g, so g = (m' - b1 m) / (1 - b1) to float32 rounding) and
# the weights it left. The system computes in bfloat16 (8 bits of mantissa)
# with float32 accumulation; the router, the GDN log-decay, beta, the L2
# norms, the cumulated decay, the decay matrix, the triangular system, U, the
# carried state and the head norm's statistic, the q/k norms, the softmax
# statistic, both sigmoid gates, the norms' statistics and the loss are
# float32. Each limit lies between two readings on the v5e (my chip runs, PR
# 68; PERF.md section 6): the largest a sound run gave and what a program
# wrong on purpose gave, with room on both sides.
#
# (A) AGAINST THE FLOAT32 REFERENCE (reference.py at "highest", handed the
# weights the step STARTED from under their HF names, the same share, and
# the system's OWN expert picks: an expert whose probability is within
# rounding of a token's 10th flips between a bfloat16-operand system and a
# float32 reference; (B) holds the picks).
HIDDEN_REL_RMS_TOL = 2.7e-2  # the residual stream after EACH of the two
                             # runs of one kind (the three Gated DeltaNet
                             # layers; the gated-attention layer), of its RMS:
                             # 2.15-2.34 % over nine sound runs (seeds
                             # 3000680001-08, steps 16 to 52; bfloat16 matmuls
                             # alone: the picks are given). Wrong on purpose
                             # (reference.py with ONE thing patched in its
                             # text, so that the system reads as wrong by the
                             # same distance; one call, seed 3000680003, step
                             # 16, where sound reads 2.31 / 2.34): the delta
                             # term left out 3.08 % (at A = U(0, 16) most
                             # heads forget their state within a position, so
                             # the correction is small: the limit lies between
                             # 2.34 and 3.08, and `gdn_decay` tells it too),
                             # attention's gate a head 3.29 (after layer 3),
                             # all 256 columns rotated 3.42, the state dropped
                             # at each chunk's start 4.96, the picks' weights
                             # not normalised 10.1, SiLU(z) before the head
                             # norm 50.5, the shared expert without its gate
                             # 54.6, the head norm's scale as 1 + w 55.7, beta
                             # = 1 58.8, q and k not L2-normalised 77.2, the
                             # key heads tiled 133, o_t from S_{t-1} 139, the
                             # decay after the update 312, a stream norm's
                             # scale as w 369. NOT seen by the stream: the q/k
                             # norms left out, 2.44 % (W_q's, W_k's and the
                             # norms' own gradients tell, below)
LOSS_ABS_TOL = 1e-3          # the loss the STEP returned, of 8.7-9.2: 4.8e-6
                             # to 2.7e-4 over the nine sound runs. Wrong on
                             # purpose: the shared expert without its gate
                             # 2.6e-2, the key heads tiled 2.8e-2, the others
                             # of the stream's list above 3.5e-2 to 0.18; the
                             # four the stream sees least stay under it (3.4e-4
                             # to 9.6e-4), and the q/k norms left out (9.4e-5)
# the gradient the step applied, every token of it, of the reference's RMS,
# the worst leaf of a family; the families are the model's own parts, so
# that a run says WHICH path broke. Sound runs -> the smallest reading of a
# wrong-on-purpose reference that the family is there to tell -> limit:
GRAD_TOLS = {
    "lnf_scale": 0.02,       # the final norm's w, the head's backward pass
                             # alone: 0.51-0.93 % -> 3.8 % (the picks' weights
                             # not normalised)
    "matrix": 0.065,         # a matrix outside the routed experts, a sum
                             # over 16,384 rows (GDN's W_qkvz, W_o; the
                             # attention layer's W_q with its gate, W_k, W_v,
                             # W_o; the shared expert; both tables): 3.8-4.2 %
                             # (the embedding) -> 8.6 % (the state dropped);
                             # the q/k norms left out 46 %, the gate a head
                             # 86 %, all columns rotated 96 %
    "expert": 0.065,         # a held expert's matrices, ~320 rows: 4.0-4.3 %
                             # -> 8.8 % (the state dropped); the picks'
                             # weights not normalised 710 %
    "router": 0.08,          # 512 probabilities a token: 4.3-5.8 % -> 9.7 %
    "vector": 0.06,          # the convolution's taps, the head norm's scale:
                             # 3.3-4.0 % -> 8.4 %
    "norm_w": 0.065,         # a zero-centred norm's stored w, the stream's
                             # eight and the attention layer's q and k norms:
                             # 3.7-4.2 % -> 8.6 %; the gate a head 27 %, all
                             # columns rotated 84 %; the q/k norms left out
                             # have no such gradient at all
    "gdn_decay": 0.06,       # A_log, dt_bias, the decay's path: 1.1-4.0 % ->
                             # 8.8 % (the delta term left out: the part beside
                             # `hidden` that tells it), 31 % the state dropped
    "gdn_ba": 0.07,          # W_ba, beta's and the decay's logits: 3.0-4.4 %
                             # -> 10.6 % (the state dropped); beta = 1 241 %
    "shared_gate": 0.07}     # w_sg, the shared expert's one gate logit:
                             # 3.7-4.5 % -> 9.6 %; without its gate the
                             # reference has no such gradient at all
UPDATE_REL_ERR_TOL = 0.3     # the step's change of the weights compared,
                             # |(p' - p) - (AdamW(p, m, v, g_ref) - p)| over
                             # |AdamW(p, m, v, g_ref) - p|, the reference's
                             # float64 AdamW (reference.adamw_after_step,
                             # rounded to the float32 a weight is kept in) on
                             # the state the step started from and the
                             # REFERENCE's gradient, the worst family: 7.3-10.2
                             # % the matrices (the embedding: rows seen once),
                             # 0.1-2.4 % the others. An AdamW without its
                             # first moment's bias correction reads 87.8 %, a
                             # state left unchanged 1. Between the largest
                             # reading and 1, more room above it
# (B) THE PICKS, against numpy float64 softmax probabilities on the router's
# OWN input rows (bfloat16 as the system rounded them) and float32 weights,
# every token of the sample, every layer: nemotron-twotower-30b-a3b's part
# (B) and its limits (laguna-xs.2's); here 0 or 1 of 655,360 picks apart, at
# <= 7.4e-8
PICKS_DIFFER_MAX_SHARE = 1e-4
NEAR_PICK_REL = 2e-5
# (C) THE SCAN'S FLOAT32 PARTS, against numpy float64 on the system's OWN
# inputs (`transformer.gdn_terms`, the first gdn layer, value heads
# OWN_HEADS, through the form of the rule the step runs): the recurrence
# over POSITIONS in float64 from ITS q, k, v (bfloat16 as the scan read
# them), ITS log-decay and beta: what holds float32 to float32 whatever the
# bfloat16 operands did
OWN_LOG_DECAY_REL_TOL = 1e-5     # G against the float64 cumulated sum:
                                 # 1.1e-7 to 1.3e-7 (granite's limit)
OWN_U_REL_RMS_TOL = 1e-5         # the triangular system's solution U: 4.0e-8
                                 # to 7.5e-8 over the nine sound runs
OWN_STATE_REL_RMS_TOL = 3e-5     # the state entering each chunk, 256 of
                                 # them: 1.5e-7 to 8.2e-7
OWN_OUT_REL_RMS_TOL = 5e-6       # the scan's output o: 1.4e-7 to 5.7e-7
OWN_HEAD_NORM_REL_RMS_TOL = 1e-5     # RMSNorm a head of ITS o times ITS
                                     # SiLU(z): 6.5e-8 to 6.6e-8
# Wrong on purpose, SYSTEM side (`gdn_terms` run again through an XLA form
# of the rule with one float32 part a precision lower; seed 3000680003, step
# 16):
# the carried state rounded to bfloat16 as each chunk leaves it reads U
# 1.6e-6, the entering states 1.65e-3, o 2.1e-5; every matmul of the scan as
# ONE bfloat16 pass (default precision for HIGHEST) U 1.66e-3, the states
# 1.93e-3, o 2.53e-3; G and the head norm do not move
OWN_HEADS = (0, 17)
COMPARED_ENTRIES = 1 << 22


def build(config, traffic, seed, devices, batches, spans):
    try:
        from hetu_tpu.models import hf_qwen3_next    # noqa: F401
    except ImportError as e:
        # a program from before PR 68 (the parent this cell is tried on
        # first): refused in one line, as a cell whose files are missing
        from benchmark.harness.manifest import ManifestError
        raise ManifestError(
            f"qwen3-next-80b-a3b: this program has no loader for it ({e}): "
            "no gdn mixer, no zero-centred norm, no gate a column") from e
    return Qwen3NextJob(config, traffic, seed, devices, batches, spans)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def _sampled(a):
    """A leaf as compared: every row of a vector or a small matrix, of a
    larger one every n-th row of its first axis, n the least that leaves at
    most COMPARED_ENTRIES entries (the float64 comparison on the host costs
    ~0.5 s a million entries). An entry of a gradient is still a sum over
    every token of the sample."""
    a = np.asarray(a)
    return a[::max(1, -(-a.size // COMPARED_ENTRIES))]


def _loads(picks, first, n_held):
    """(layers, E) picks an expert -> ([the fullest expert's load over the
    mean, a layer], the share of all picks on the experts held, in %)."""
    picks = np.asarray(picks, np.float64)
    return ((picks.max(-1) / picks.mean(-1)).tolist(),
            100.0 * picks[:, first:first + n_held].sum() / picks.sum())


def _picks_f64(router_in, router, experts):
    """Part (B): every layer's picks `experts` (L, S, k) against the k
    largest of float64 softmax(x W) on the router's own input rows
    `router_in` (L, S, D) and weights `router` (L, D, E) -> (picks checked,
    picks that differ, the largest |p64 - theta64| / max |p64| over the
    experts on one side only)."""
    checked = differ = 0
    worst = 0.0
    k = experts.shape[-1]
    for x, w, ours_e in zip(router_in, router, experts):
        z = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
        z = np.exp(z - z.max(1, keepdims=True))
        z /= z.sum(1, keepdims=True)
        order = np.argsort(-z, axis=1, kind="stable")
        theta = np.take_along_axis(z, order[:, k - 1:k], 1)
        want, ours = (np.zeros(z.shape, bool) for _ in range(2))
        np.put_along_axis(want, order[:, :k], True, 1)
        np.put_along_axis(ours, np.asarray(ours_e), True, 1)
        off = ours != want
        checked += ours_e.size
        differ += int((ours & ~want).sum())
        if off.any():
            worst = max(worst, float((
                np.abs(z - theta) / np.abs(z).max(1, keepdims=True))[off]
                .max()))
    return checked, differ, worst


def _own_terms_f64(t, chunk, eps):
    """Part (C) in numpy float64 from the system's own inputs -> the errors
    of the scan's float32 parts: G against the cumulated sum of ITS g over
    each chunk; then the recurrence over POSITIONS on ITS q, k, v, g and beta
    (S' = exp(g) S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q): the
    system's solution U against u, the state entering each chunk against S
    at the chunks' starts, the output against o; and the gated head norm
    against RMSNorm a head of ITS o times ITS gate."""
    f64 = lambda x: np.asarray(x).astype(np.float64)
    q, k, v, g, beta = (f64(t[n])[0] for n in ("q", "k", "v", "g", "beta"))
    T, H, K = q.shape
    G = np.concatenate([np.cumsum(g[i:i + chunk], 0)
                        for i in range(0, T, chunk)])
    S = np.zeros((H, K, v.shape[-1]))
    U, o, entering = np.empty_like(v), np.empty_like(v), []
    for i in range(T):
        if i % chunk == 0:
            entering.append(S.copy())
        S *= np.exp(g[i])[:, None, None]
        U[i] = beta[i][:, None] * (v[i] - np.einsum("hkv,hk->hv", S, k[i]))
        S += k[i][..., None] * U[i][:, None, :]
        o[i] = np.einsum("hkv,hk->hv", S, q[i])
    got_o = f64(t["o"])[0]
    normed = (got_o / np.sqrt(np.mean(got_o ** 2, -1, keepdims=True) + eps)
              * f64(t["scale"])).reshape(T, -1) * f64(t["gate"])[0]
    return {"own_log_decay_rel_rms_err": _rel_rms(f64(t["G"])[0], G),
            "own_u_rel_rms_err": _rel_rms(f64(t["U"])[0], U),
            "own_entering_state_rel_rms_err": _rel_rms(
                f64(t["entering"])[0], np.stack(entering)),
            "own_entering_state_rms": float(np.sqrt(np.mean(
                np.stack(entering) ** 2))),
            "own_out_rel_rms_err": _rel_rms(got_o, o),
            "own_head_norm_rel_rms_err": _rel_rms(f64(t["normed"])[0],
                                                  normed),
            "chunk_log_decay_min": float(t["chunk_log_decay_min"])}


class Qwen3NextJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_qwen3_next, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_qwen3_next.config_from_hf(
            config, dtype=jnp.bfloat16,
            router_aux_loss_coef=config["assumed"]["router_aux_loss_coef"])
        self.items_per_step = traffic["sequences"] * traffic["seq_len"]

        def init(key):
            params = tfm.init_params(key, cfg)
            return params, tfm.init_opt_state(params)

        # weights and optimizer state on the device, in one call
        self.params, self.opt = jax.jit(init)(jax.random.PRNGKey(seed))
        self._step = tfm.make_train_step(
            cfg, lr=config["assumed"]["learning_rate"])
        # tokens are an argument, not a constant: one program for every batch
        self._routing = jax.jit(
            lambda params, tokens: tfm.moe_routing_stats(params, tokens, cfg))
        self.batches = batches
        self._i = 0
        self._loss = None
        self._traced_picks = None
        self._moe = self._gdn = None

    def step(self):
        import jax
        with self.spans("feed"):
            batch = jax.device_put(
                self.batches[self._i % len(self.batches)], self.device)
            self._i += 1
        with self.spans("step_call"):
            self._loss, self.params, self.opt = self._step(
                self.params, self.opt, batch["tokens"], batch["targets"])

    def _count_traced_picks(self):
        """The pick counter of a traced run, smallthinker-21b-a3b's way: the
        router has no selection bias beside which the step could write its
        counts, so the picks of the traced steps are what the program's own
        routing pass (`moe_routing_stats`, the function the check's part (B)
        holds to float64) takes on THOSE steps' batches at the weights the
        traced window starts from. Made once, when the warm-up's last step
        has drained and before the profiler opens: nothing of it is in the
        trace. The weights move by at most lr (3e-6) a step in the five
        traced steps: these counts are the routing PASS's, not the traced
        steps' own (PERF.md section 7)."""
        import jax
        picks = []
        for j in range(self.traffic["trace_steps"]):
            batch = self.batches[(self._i + j) % len(self.batches)]
            stats = self._routing(self.params, jax.device_put(
                batch["tokens"], self.device))
            picks.append(np.asarray(stats["picks"]).tolist())
        return picks

    def sync(self):
        with self.spans("sync"):
            loss = float(self._loss)
        if (self.spans.enabled and self._traced_picks is None
                and self._i == self.traffic.get("warmup_steps", 3)):
            self._traced_picks = self._count_traced_picks()
        return loss

    def counters(self):
        from benchmark.reduce import gdn
        out = {"flops_per_item": gdn.flops_per_token(
            self.config, self.traffic["seq_len"], self.cfg.gdn.chunk)}
        if self._moe is not None:
            out["moe"] = self._moe
        if self._gdn is not None:
            out["gdn"] = self._gdn
        if self._traced_picks:
            out["traced_picks"] = self._traced_picks
        return out

    def _hf_names(self):
        """{a family of GRAD_TOLS: the groups of HF names whose gradients it
        covers}: a group is one leaf of one layer (the held experts'
        matrices of a layer are one); the worst group is reported."""
        from hetu_tpu.models import hf_qwen3_next as hq
        from hetu_tpu.models import transformer as tfm
        cfg = self.cfg
        kinds = tfm.layer_kinds(cfg)
        gdn = [i for i, k in enumerate(kinds) if tfm.mixer_of(k) == "gdn"]
        att = [i for i, k in enumerate(kinds)
               if tfm.mixer_of(k) == "attention"]
        every = range(len(kinds))
        first = cfg.router.first_held
        at = lambda layers, part: [[hq.hf_name(i, part)] for i in layers]
        return {
            "lnf_scale": [[hq.FINAL_NORM]],
            "matrix": (
                # the first layer of its kind for the large matrices
                at(gdn[:1], hq.GDN_QKVZ) + at(gdn[:1], hq.GDN_OUT)
                + [[hq.hf_name(i, p)] for i in att
                   for p in (hq.ATTN_Q, hq.ATTN_K, hq.ATTN_V, hq.ATTN_O)]
                + [[hq.shared_name(0, w)] for w in hq.MLP]
                + [[hq.EMBED], [hq.HEAD]]),
            "expert": [[hq.expert_name(0, first + e, w)
                        for e in range(cfg.n_experts)] for w in hq.MLP],
            "router": at(every, hq.ROUTER),
            "vector": (at(gdn, hq.GDN_CONV)
                       + at(gdn, hq.GDN_VECTORS["gdn_norm"])),
            "norm_w": ([[hq.hf_name(i, p)] for i in every
                        for p in hq.NORMS.values()]
                       + [[hq.hf_name(i, p)] for i in att
                          for p in hq.ATTN_NORMS.values()]),
            "gdn_decay": (at(gdn, hq.GDN_VECTORS["gdn_A_log"])
                          + at(gdn, hq.GDN_VECTORS["gdn_dt_bias"])),
            "gdn_ba": at(gdn, hq.GDN_BA),
            "shared_gate": at(every, hq.SHARED_GATE)}

    def check(self, reference):
        """One more call of the timed step on the correctness sample
        (`_observe`), and what it returned against the float32 reference
        GIVEN the system's own picks (`_compare`): its loss, the gradient it
        applied, the weights it left (the reference's AdamW); the residual
        stream after each run of one kind. Part (B): the picks against
        float64 probabilities on the routers' own input rows. Part (C): the
        scan's and the head norm's float32 parts against float64 on the
        system's own inputs."""
        return self._compare(reference, self._observe())

    def _observe(self):
        """The system's side of the check, nothing of the reference: what
        the timed step returned on the sample, and what the program's own
        pure functions (`_through_run`, `moe_routing_stats`, `gdn_terms`)
        make of the weights it started from."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_qwen3_next, transformer as tfm
        from benchmark.generators import lm_zipf

        cfg, config = self.cfg, self.config
        sample = jax.device_put(lm_zipf.generate(
            self.traffic, config, self.seed,
            sequences=self.traffic["check_sequences"])[0], self.device)
        tokens, targets = sample["tokens"], sample["targets"]
        wanted = sorted(h for groups in self._hf_names().values()
                        for group in groups for h in group)
        t0 = time.perf_counter()

        # the compared leaves of a tree shaped like the weights (the weights,
        # an AdamW slot), under their HF names, on the host: brought over a
        # leaf at a time and renamed there, so that nothing new stands on the
        # device beside the state (three trees' leaves at once would)
        cpu = jax.devices("cpu")[0]
        host = lambda tree: jax.tree.map(np.asarray, tree)

        def compared(tree):
            with jax.default_device(cpu):
                sd = hf_qwen3_next.state_dict_from_params(tree, cfg)
                return {n: _sampled(sd[n]) for n in wanted}

        # THE TIMED STEP, once more. It gives its arguments' buffers away:
        # the state it starts from goes to the host first (the whole of the
        # weights: the reference and the check's other programs read them)
        start = host(self.params)
        before = {"p": compared(start), "m": compared(host(self.opt["m"])),
                  "v": compared(host(self.opt["v"]))}
        step_no = float(self.opt["t"]) + 1.0
        step_loss, self.params, self.opt = self._step(
            self.params, self.opt, tokens, targets)
        step_loss = float(step_loss)
        after = {"p": compared(host(self.params)),
                 "m": compared(host(self.opt["m"]))}
        # the job is over: its 10 GB are the check's. The weights the step
        # started from, bit for bit, for every program below
        self.opt = self.params = None
        params = jax.device_put(start, self.device)
        del start
        t1 = time.perf_counter()

        # tokens are arguments, not constants of the programs: every seed
        # then reads the same entries of the compile cache
        def hidden_and_routing(params, tokens):
            h, after = tfm.embed_tokens(params, tokens, cfg), []
            for (kind, _), blocks in zip(
                    tfm.layer_runs(cfg),
                    tfm.run_blocks(cfg, params["blocks"])):
                h = tfm._through_run(h, blocks, cfg, kind)
                after.append(h.astype(jnp.float32))
            return after, tfm.moe_routing_stats(params, tokens, cfg,
                                                terms=True)

        stream, stats = jax.device_get(jax.jit(hidden_and_routing)(
            params, tokens))
        router_w = np.concatenate(
            [np.asarray(b["router"])
             for b in tfm.run_blocks(cfg, params["blocks"])])
        picks = _picks_f64(stats.pop("router_in"), router_w,
                           stats["experts"])
        del router_w
        own_terms = _own_terms_f64(
            jax.device_get(jax.jit(lambda p, t: tfm.gdn_terms(
                p, t, cfg, heads=tuple(
                    h for h in OWN_HEADS if h < cfg.gdn.n_v_heads)))(
                        params, tokens)),
            cfg.gdn.chunk, cfg.ln_eps)
        sd = hf_qwen3_next.state_dict_from_params(params, cfg)
        del params             # the reference holds its own (HF) views now
        return {"tokens": tokens, "targets": targets, "wanted": wanted,
                "before": before, "after": after, "step_no": step_no,
                "step_loss": step_loss, "stream": stream, "stats": stats,
                "picks": picks, "own_terms": own_terms, "sd": sd,
                "seconds": {"step": t1 - t0,
                            "system": time.perf_counter() - t1}}

    def _compare(self, reference, seen):
        """`_observe`'s findings against reference.py, eagerly (the
        reference jits its layers and head itself) -> the check's result,
        every part by name in `failed_parts`."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import transformer as tfm

        cfg, config = self.cfg, self.config
        r = cfg.router
        tokens, targets, sd = seen["tokens"], seen["targets"], seen["sd"]
        before, after, stats = seen["before"], seen["after"], seen["stats"]
        hf_names = self._hf_names()
        t2 = time.perf_counter()
        loads, held_pct = _loads(stats["picks"], r.first_held, cfg.n_experts)
        self._moe = {"picks": stats["picks"].tolist(),
                     "max_over_mean": loads,
                     "held": stats["held"].tolist(),
                     "dropped": int(stats["dropped"].sum()),
                     "entropy": stats["entropy"].tolist()}
        self._gdn = {"chunk_log_decay_min":
                     seen["own_terms"]["chunk_log_decay_min"]}

        # part (A): ONE pass of the reference, forward and backward, given
        # the picks
        picks = list(jnp.asarray(stats["experts"]))
        want_loss, want_hidden, want_grads = reference.grads_of(
            seen["wanted"])(sd, tokens, targets, config, picks=picks)
        hidden_err = {
            f"after_layer_{layers[-1]}_{tfm.mixer_of(kind)}": _rel_rms(
                got, want_hidden[layers[-1]])
            for (kind, layers), got in zip(tfm.run_layers(cfg),
                                           seen["stream"])}
        want_loss = float(want_loss)
        want_grads = {n: _sampled(g) for n, g in jax.device_get(
            want_grads).items()}
        del want_hidden
        t3 = time.perf_counter()
        # the gradient the step applied, from AdamW's first moment; and the
        # weights it left against the reference's AdamW on its own gradient
        adamw = config["assumed"]["adamw"]
        b1 = adamw["b1"]
        f64 = lambda a: np.asarray(a, np.float64).reshape(-1)
        pooled = lambda tree, group: np.concatenate(
            [f64(tree[h]) for h in group])
        grad_err, grad_worst, update_err = {}, {}, {}
        for n, groups in hf_names.items():
            grad_err[n] = update_err[n] = 0.0
            for group in groups:
                p, m, v, g = (pooled(tree, group) for tree in (
                    before["p"], before["m"], before["v"], want_grads))
                got_g = (pooled(after["m"], group) - b1 * m) / (1.0 - b1)
                # rounded to the float32 a weight is kept in
                want_p = reference.adamw_after_step(
                    p, m, v, g, seen["step_no"],
                    config["assumed"]["learning_rate"], adamw).astype(
                        np.float32).astype(np.float64)
                err = _rel_rms(got_g, g)
                if err >= grad_err[n]:
                    grad_err[n], grad_worst[n] = err, group[0]
                update_err[n] = max(update_err[n], _rel_rms(
                    pooled(after["p"], group) - p, want_p - p))
        t4 = time.perf_counter()

        picks_checked, picks_differ, picks_worst = seen["picks"]
        step_loss = seen["step_loss"]
        own = seen["own_terms"]
        n_picks = float(np.asarray(stats["picks"]).sum(-1)[0])
        out = {"loss": step_loss, "reference_loss": want_loss,
               "loss_abs_err": abs(step_loss - want_loss),
               "hidden_rel_rms_err": hidden_err,
               "picks_checked": picks_checked,
               "picks_that_differ": picks_differ,
               "picks_differ_share": picks_differ / max(picks_checked, 1),
               "picks_differ_worst_distance": picks_worst,
               "held_picks": self._moe["held"],
               "held_pick_pct": held_pct,
               "held_pick_pct_by_layer": [
                   100.0 * h / n_picks for h in self._moe["held"]],
               "dropped_picks": self._moe["dropped"],
               "load_max_over_mean": loads,
               **own,
               "step": seen["step_no"],
               "grad_rel_rms_err": grad_err,
               "grad_worst_leaf": grad_worst,
               "update_rel_err": update_err,
               "sample": list(tokens.shape),
               "seconds": {**seen["seconds"],
                           "reference": t3 - t2,
                           "host_comparison": t4 - t3}}
        # every part by name: `failed_parts` says which limits a run broke
        parts = {
            "loss": bool(np.isfinite(out["loss"])
                         and out["loss_abs_err"] <= LOSS_ABS_TOL),
            "hidden": (len(hidden_err) == len(tfm.layer_runs(cfg))
                       and max(hidden_err.values()) <= HIDDEN_REL_RMS_TOL),
            "picks": (out["picks_differ_share"] <= PICKS_DIFFER_MAX_SHARE
                      and out["picks_differ_worst_distance"] <= NEAR_PICK_REL
                      and out["dropped_picks"] == 0),
            "own_log_decay":
                own["own_log_decay_rel_rms_err"] <= OWN_LOG_DECAY_REL_TOL,
            "own_solve": own["own_u_rel_rms_err"] <= OWN_U_REL_RMS_TOL,
            "own_state": (own["own_entering_state_rel_rms_err"]
                          <= OWN_STATE_REL_RMS_TOL
                          and own["own_entering_state_rms"] > 0.0),
            "own_out": own["own_out_rel_rms_err"] <= OWN_OUT_REL_RMS_TOL,
            "own_head_norm": (own["own_head_norm_rel_rms_err"]
                              <= OWN_HEAD_NORM_REL_RMS_TOL),
            **{"grads_" + n: err <= GRAD_TOLS[n]
               for n, err in grad_err.items()},
            "update": (set(grad_err) == set(GRAD_TOLS)
                       and max(update_err.values()) <= UPDATE_REL_ERR_TOL)}
        out["failed_parts"] = [n for n, ok in parts.items() if not ok]
        out["ok"] = not out["failed_parts"]
        return out

    def close(self):
        pass
