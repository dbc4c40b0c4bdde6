"""kimi-linear-48b-a3b as a user's job script builds it: the published
config.json (cut to one chip's share, config.json `reduced`) through
`hf_kimi_linear.config_from_hf`, weights from the program's own initialiser,
`transformer.make_train_step` (next-token loss on the untied head, AdamW in
the step, the routers' selection bias by its sign rule after it). Only
architecture, shapes, optimizer, the bias's rate and compute dtype are
stated; the chunked rule's form, attention implementation, fused
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
# = b1 m + (1 - b1) g, so g = (m' - b1 m) / (1 - b1) to float32 rounding),
# the weights it left, the picks it counted and the bias it moved. The system
# computes in bfloat16 (8 bits of mantissa) with float32 accumulation; the
# router, the KDA log-decay, beta, the L2 norms, the cumulated decay, the
# pairwise products, the triangular system, U, the carried state and the
# head norm's statistic, the latent's norm, the softmax statistic, the norms'
# statistics and the loss are float32. Each limit lies between two readings
# on the v5e (my chip runs, PR 66; seventeen sound runs, seeds 3000660001-03,
# -11 to -17 and -41 to -47, at steps 16 to 35; PERF.md section 6): the largest a sound run
# gave and what a program wrong on purpose gave, with
# room on both sides.
#
# (A) AGAINST THE FLOAT32 REFERENCE (reference.py at "highest", handed the
# weights the step STARTED from under their HF names, the same share, and
# the system's OWN expert picks: an expert whose score is within rounding of
# a token's 8th flips between a bfloat16-operand system and a float32
# reference; (B) holds the picks).
HIDDEN_REL_RMS_TOL = 3e-2    # the residual stream after EACH of the four
                             # runs of one kind (the model's forward on the
                             # weights the step started from), of its RMS:
                             # 0.92-0.96 % after layer 0, 1.09-1.41 % after
                             # layer 4 over the seventeen sound runs: bfloat16 matmuls
                             # alone (with the picks given no flipped pick
                             # adds). Wrong on purpose (reference.py with ONE
                             # thing patched in its text, so that the system
                             # reads as wrong by the same distance; one call,
                             # seed 3000660003, step 16): the decay applied
                             # AFTER the update 9.4 % (13.2 % after layer 4),
                             # the gate before the head norm 25.6 %, o_t read
                             # from S_{t-1} 33.9 %, beta = 1 49.9 %, the delta
                             # term left out 56.9 %, the state dropped at each
                             # chunk's start 73.3 %, the decay a head 93.9 %;
                             # q and k not L2-normalised: the reference's
                             # state overflows (NaN). The head norm rescales
                             # the scan's output to unit RMS, so the stream
                             # SEES the recurrence here (unlike the nemotron
                             # cell's, R17(f)). NOT seen by the stream: the
                             # latent layer's key rotated, 1.58 % (1.41 sound):
                             # W_q's gradient tells, below
LOSS_ABS_TOL = 1e-3          # the loss the STEP returned, of 7.3-7.7: 1.5e-5
                             # to 3.4e-4 over the seventeen sound runs. Wrong on
                             # purpose: o_t from S_{t-1} 1.06e-3, the decay
                             # after the update 2.2e-3, the gate before the
                             # norm 1.6e-2, the others 0.19-0.34; the rotated
                             # key (2.2e-4) stays under it
# the gradient the step applied, every token of it, of the reference's RMS,
# the worst leaf of a family; the families are the recurrence's own parts,
# so that a run says WHICH path broke. Sound runs -> the smallest reading of
# a wrong-on-purpose reference (the decay applied after the update, but for
# "matrix") -> limit:
GRAD_TOLS = {
    "lnf_scale": 0.02,       # the head's backward pass alone: 0.39-0.84 % ->
                             # 4.5 %
    "matrix": 0.08,          # a matrix outside the routed experts, a sum
                             # over 16,384 rows (KDA's W_q, W_k, W_v, W_o,
                             # W_ga, W_gb; the latent mixer's four; the dense
                             # MLP, the shared expert; both tables): 2.4-2.8 %
                             # (the embedding, W_ga) -> 22.6 %; the latent
                             # layer's key rotated 84.9 % (W_q of layer 3),
                             # the ONLY part that sees it
    "expert": 0.2,           # a held expert's matrices, ~512 rows: 11.4-13.7 %
                             # -> 25.4 %
    "router": 0.4,           # a difference of near equal terms over 256
                             # scores a token: 18.0-24.4 % -> 32.8 % (under
                             # the limit; 78 % and more for the other six)
    "vector": 0.07,          # norms' scales, the convolutions' taps, the head
                             # norm's scale: 2.0-2.5 % (q's taps) -> 18.9 %
    "kda_decay": 0.1,        # A_log, dt_bias, W_fa, W_fb, the decay's path:
                             # 2.2-2.9 % -> 41.4 % (W_fb); the decay a head
                             # 384 %, the state dropped 199 %
    "kda_beta": 0.1}         # W_b, the step's path: 2.3-3.0 % -> 24.7 %;
                             # beta = 1 has no such gradient at all
UPDATE_REL_ERR_TOL = 0.3     # the step's change of the weights compared,
                             # |(p' - p) - (AdamW(p, m, v, g_ref) - p)| over
                             # |AdamW(p, m, v, g_ref) - p|, the reference's
                             # float64 AdamW (reference.adamw_after_step,
                             # rounded to the float32 a weight is kept in) on
                             # the state the step started from and the
                             # REFERENCE's gradient, the worst family: 0.6-0.9
                             # % beta's and the final norm's, 1.7-2.2 % the
                             # vectors, 4.0-4.6 % the experts and the decay's
                             # path, 6.6-7.6 % the matrices (the embedding:
                             # rows seen once), 8.3-12.6 % the routers. An
                             # AdamW without its first moment's bias
                             # correction reads 87.6-87.8 % in every family,
                             # a state left unchanged 1. Between the largest
                             # reading and 1, more room above it
# (B) THE PICKS, against numpy float64 scores on the router's OWN input rows
# (bfloat16 as the system rounded them), float32 weights and the bias, every
# token of the sample, every expert layer: nemotron-twotower-30b-a3b's part
# (B) and its limits (laguna-xs.2's)
PICKS_DIFFER_MAX_SHARE = 1e-4
NEAR_PICK_REL = 2e-5
STEP_PICKS_MOVED_MAX_SHARE = 1e-2
# (C) THE SCAN'S FLOAT32 PARTS, against numpy float64 on the system's OWN
# inputs (`transformer.kda_terms`, the first kda layer, heads OWN_HEADS): the
# recurrence over POSITIONS in float64 from ITS q, k, v (bfloat16 as the scan
# read them), ITS log-decay and beta: what holds float32 to float32 whatever
# the bfloat16 operands did
OWN_LOG_DECAY_REL_TOL = 1e-5     # G against the float64 cumulated sum:
                                 # 1.2e-7 on every run (granite's limit)
OWN_U_REL_RMS_TOL = 1e-5         # the triangular system's solution U:
                                 # 3.2e-7 to 7.5e-7 over the seventeen sound runs
OWN_STATE_REL_RMS_TOL = 3e-5     # the state entering each chunk, 256 of
                                 # them: 1.4e-6 to 2.8e-6
OWN_OUT_REL_RMS_TOL = 3e-5       # the scan's output o: 1.7e-6 to 2.7e-6
OWN_HEAD_NORM_REL_RMS_TOL = 1e-5     # RMSNorm a head of ITS o times ITS gate:
                                     # 6.5e-8 to 6.8e-8 (nemotron's limit)
# Wrong on purpose, SYSTEM side (`kda_terms` run again with one float32 part
# a precision lower; seed 3000660004, step 16): the carried state rounded to
# bfloat16 as each chunk leaves it reads U 1.4e-4, the entering states 1.7e-3,
# o 7.4e-4; every matmul of the scan as ONE bfloat16 pass (default precision
# for HIGHEST) U 1.8e-3, the states 1.5e-3, o 1.6e-3; G and the head norm do
# not move (neither is a matmul nor the state). Each limit lies a decade or
# more from both readings
OWN_HEADS = (0, 17)
COMPARED_ENTRIES = 1 << 22


def build(config, traffic, seed, devices, batches, spans):
    try:
        from hetu_tpu.models import hf_kimi_linear    # noqa: F401
    except ImportError as e:
        # a program from before PR 66 (the parent this cell is tried on
        # first): refused in one line, as a cell whose files are missing
        from benchmark.harness.manifest import ManifestError
        raise ManifestError(
            f"kimi-linear-48b-a3b: this program has no loader for it ({e}): "
            "no kda mixer, no latent attention without rotation") from e
    return KimiLinearJob(config, traffic, seed, devices, batches, spans)


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


def _picks_f64(router_in, router, bias, experts):
    """Part (B): every layer's picks `experts` (L, S, k) against the k
    largest of float64 sigmoid(x W) + b on the router's own input rows
    `router_in` (L, S, D), weights `router` (L, D, E) and bias (L, E) ->
    (picks checked, picks that differ, the largest |z64 - theta64| / max
    |z64| over the experts on one side only)."""
    checked = differ = 0
    worst = 0.0
    k = experts.shape[-1]
    for x, w, b, ours_e in zip(router_in, router, bias, experts):
        z = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                  @ np.asarray(w, np.float64)))) + np.asarray(
            b, np.float64)
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
    (S' = Diag(exp g) S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q):
    the system's solution U against u, the state entering each chunk against
    S at the chunks' starts, the output against o; and the gated head norm
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
        S *= np.exp(g[i])[..., None]
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


def _loads(picks, first, n_held):
    """(layers, E) picks an expert -> ([the fullest expert's load over the
    mean, a layer], the share of all picks on the experts held, in %)."""
    picks = np.asarray(picks, np.float64)
    return ((picks.max(-1) / picks.mean(-1)).tolist(),
            100.0 * picks[:, first:first + n_held].sum() / picks.sum())


class KimiLinearJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_kimi_linear, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_kimi_linear.config_from_hf(
            config, dtype=jnp.bfloat16,
            router_bias_rate=config["assumed"]["expert_bias_update_rate"])
        self.items_per_step = traffic["sequences"] * traffic["seq_len"]

        def init(key):
            params = tfm.init_params(key, cfg)
            return params, tfm.init_opt_state(params)

        # weights and optimizer state on the device, in one call
        self.params, self.opt = jax.jit(init)(jax.random.PRNGKey(seed))
        self._step = tfm.make_train_step(
            cfg, lr=config["assumed"]["learning_rate"])
        # the program's counter: the picks each expert took in the last
        # step, which `move_router_bias` wrote into the bias's first AdamW
        # slot; (expert layers, 256). In a traced run a copy is kept a step
        # (one small device op, no host read); else read at a sync
        self._bias_leaves = lambda tree: jnp.concatenate(
            [b[tfm.ROUTER_BIAS] for b in tfm.run_blocks(cfg, tree["blocks"])
             if tfm.ROUTER_BIAS in b])
        self._picks_of = jax.jit(self._bias_leaves)
        self.batches = batches
        self._i = 0
        self._loss = None
        self._step_picks = []      # traced runs: a device array a step
        self._sync_picks = []      # (steps done, picks of the last step)
        self._moe = self._kda = None

    def step(self):
        import jax
        with self.spans("feed"):
            batch = jax.device_put(
                self.batches[self._i % len(self.batches)], self.device)
            self._i += 1
        with self.spans("step_call"):
            self._loss, self.params, self.opt = self._step(
                self.params, self.opt, batch["tokens"], batch["targets"])
            if self.spans.enabled:
                self._step_picks.append(self._picks_of(self.opt["m"]))

    def sync(self):
        with self.spans("sync"):
            loss = float(self._loss)
            self._sync_picks.append(
                (self._i, np.asarray(self._picks_of(self.opt["m"]))))
            return loss

    def counters(self):
        from benchmark.reduce import kda
        out = {"flops_per_item": kda.flops_per_token(
            self.config, self.traffic["seq_len"], self.cfg.kda.chunk)}
        if self._moe is not None:
            out["moe"] = self._moe
        if self._kda is not None:
            out["kda"] = self._kda
        if self._step_picks:
            # the traced window's steps come first after the warm-up
            warm = self.traffic.get("warmup_steps", 3)
            steps = self._step_picks[warm:warm + self.traffic["trace_steps"]]
            out["traced_picks"] = [np.asarray(p).tolist() for p in steps]
        return out

    def _hf_names(self):
        """{a family of GRAD_TOLS: the groups of HF names whose gradients it
        covers}: a group is one leaf of one layer (the held experts'
        matrices of a layer are one); the worst group is reported."""
        from hetu_tpu.models import hf_kimi_linear as hk
        from hetu_tpu.models.hf_common import (MLA_KV_B, MLA_KV_NORM,
                                               MLA_LINEARS)
        from hetu_tpu.models import transformer as tfm
        cfg = self.cfg
        kinds = tfm.layer_kinds(cfg)
        kda = [i for i, k in enumerate(kinds) if tfm.mixer_of(k) == "kda"]
        mla = [i for i, k in enumerate(kinds) if tfm.mixer_of(k) == "mla"]
        moe = [i for i, k in enumerate(kinds) if tfm.experts_of(cfg, k)]
        dense = [i for i in range(len(kinds)) if i not in moe]
        first = cfg.router.first_held
        at = lambda layers, part: [[hk.hf_name(i, part)] for i in layers]
        lin = hk.KDA_LINEARS
        return {
            "lnf_scale": [[hk.FINAL_NORM]],
            "matrix": (
                # the first layer of its kind for the large matrices
                [[hk.hf_name(kda[0], p)] for p in hk.KDA_QKV]
                + at(kda[:1], lin["kda_wo"]) + at(kda, lin["kda_ga"])
                + at(kda, lin["kda_gb"])
                + [[hk.hf_name(i, p)] for i in mla
                   for p in (*MLA_LINEARS.values(), MLA_KV_B)]
                + [[hk.hf_name(i, "mlp." + p)] for i in dense
                   for p in hk.MLP.values()]
                + [[hk.shared_name(moe[0], w)] for w in hk.MLP]
                + [[hk.EMBED], [hk.HEAD]]),
            "expert": [[hk.expert_name(moe[0], first + e, w)
                        for e in range(cfg.n_experts)] for w in hk.MLP],
            "router": at(moe, hk.ROUTER),
            "vector": (
                [[hk.hf_name(i, p)] for i in range(len(kinds))
                 for p in hk.NORMS.values()]
                + [[hk.hf_name(i, p)] for i in kda for p in hk.KDA_CONVS]
                + at(kda, hk.KDA_VECTORS["kda_norm"])
                + at(mla, MLA_KV_NORM)),
            "kda_decay": (
                at(kda, hk.KDA_VECTORS["kda_A_log"])
                + at(kda, hk.KDA_VECTORS["kda_dt_bias"])
                + at(kda, lin["kda_fa"]) + at(kda, lin["kda_fb"])),
            "kda_beta": at(kda, lin["kda_wb"])}

    def check(self, reference):
        """One more call of the timed step on the correctness sample
        (`_observe`), and what it returned against the float32 reference
        GIVEN the system's own picks (`_compare`): its loss, the gradient it
        applied, the weights it left (the reference's AdamW), the bias it
        moved (the reference's rule); the residual stream after each run of
        one kind. Part (B): the picks against float64 scores on the routers'
        own input rows. Part (C): the scan's and the head norm's float32
        parts against float64 on the system's own inputs."""
        return self._compare(reference, self._observe())

    def _observe(self):
        """The system's side of the check, nothing of the reference: what
        the timed step returned on the sample, and what the program's own
        pure functions (`_through_run`, `moe_routing_stats`, `kda_terms`)
        make of the weights it started from."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_kimi_linear, transformer as tfm
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
                sd = hf_kimi_linear.state_dict_from_params(tree, cfg)
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
        bias_moved = np.asarray(self._picks_of(self.params))
        step_counts = np.asarray(self._picks_of(self.opt["m"]))
        # the job is over: its 9.6 GB are the check's. The weights the step
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
        bias = np.asarray(self._picks_of(params))
        router_w = np.concatenate(
            [np.asarray(b["router"])
             for b in tfm.run_blocks(cfg, params["blocks"])
             if "router" in b])
        picks = _picks_f64(stats.pop("router_in"), router_w, bias,
                           stats["experts"])
        del router_w
        own_terms = _own_terms_f64(
            jax.device_get(jax.jit(lambda p, t: tfm.kda_terms(
                p, t, cfg, heads=tuple(
                    h for h in OWN_HEADS if h < cfg.kda.n_heads)))(
                        params, tokens)),
            cfg.kda.chunk, cfg.ln_eps)
        sd = hf_kimi_linear.state_dict_from_params(params, cfg)
        del params             # the reference holds its own (HF) views now
        return {"tokens": tokens, "targets": targets, "wanted": wanted,
                "before": before, "after": after, "step_no": step_no,
                "step_loss": step_loss, "bias": bias,
                "bias_moved": bias_moved, "step_counts": step_counts,
                "stream": stream, "stats": stats, "picks": picks,
                "own_terms": own_terms, "sd": sd,
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
        r, rate = cfg.router, cfg.router.bias_rate
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
        self._kda = {"chunk_log_decay_min":
                     seen["own_terms"]["chunk_log_decay_min"]}

        # part (A): ONE pass of the reference, forward and backward, given
        # the picks (its counts are then the handed picks' own)
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
        want_counts = np.stack([np.bincount(
            np.asarray(e).reshape(-1), minlength=r.width or cfg.n_experts)
            for e in stats["experts"]])
        del want_hidden
        # the bias the step left: the system's rule on the picks the STEP
        # counted against the reference's rule on the picks handed to it. An
        # entry may differ only where the picks the step's own forward pass
        # counted moved it across the mean
        want_bias = reference.bias_after_step(seen["bias"], want_counts, rate)
        differs = np.abs(seen["bias_moved"] - want_bias) > rate / 2
        moved = np.abs(seen["step_counts"] - want_counts)
        near = np.abs(want_counts - want_counts.mean(-1, keepdims=True)
                      ) <= moved.sum(-1, keepdims=True)
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
        out = {"loss": step_loss, "reference_loss": want_loss,
               "loss_abs_err": abs(step_loss - want_loss),
               "hidden_rel_rms_err": hidden_err,
               "picks_checked": picks_checked,
               "picks_that_differ": picks_differ,
               "picks_differ_share": picks_differ / max(picks_checked, 1),
               "picks_differ_worst_distance": picks_worst,
               "held_picks": self._moe["held"],
               "held_pick_pct": held_pct,
               "dropped_picks": self._moe["dropped"],
               "load_max_over_mean": loads,
               "step_picks_moved_share": float(
                   moved.sum() / 2 / max(want_counts.sum(), 1)),
               "bias_entries_that_differ": int(differs.sum()),
               "bias_entries_unexplained": int((differs & ~near).sum()),
               **own,
               "step": seen["step_no"],
               "grad_rel_rms_err": grad_err,
               "grad_worst_leaf": grad_worst,
               "update_rel_err": update_err,
               "by_sync": [
                   dict(zip(("steps", "load_max_over_mean", "held_pick_pct"),
                            (i,) + _loads(p, r.first_held, cfg.n_experts)))
                   for i, p in self._sync_picks],
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
            "step_picks": (out["step_picks_moved_share"]
                           <= STEP_PICKS_MOVED_MAX_SHARE
                           and out["bias_entries_unexplained"] == 0),
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
