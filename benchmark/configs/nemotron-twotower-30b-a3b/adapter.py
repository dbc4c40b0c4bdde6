"""nemotron-twotower-30b-a3b as a user's job script builds it: the published
config.json (cut to one chip's share, config.json `reduced`) through
`hf_nemotron_h.config_from_hf`, weights from the program's own initialiser,
`transformer.make_train_step` (next-token loss on the untied head, AdamW in
the step, the routers' selection bias by its sign rule after it). Only
architecture, shapes, optimizer, the bias's rate and compute dtype are
stated; attention implementation, fused cross-entropy, recomputation, the
scan's kernels, the grouped matmul and kernel mode stay the program's
defaults. The model's denoiser tower and its block-diffusion objective are
not supported and not here (config.json `recipe`).
"""
import time

import numpy as np

# The check holds the TIMED program: ONE more call of the job's own compiled
# step (`self._step`, the program the window timed, at its 8,192 tokens) on
# the correctness sample (one sequence, another stream of the same seed, the
# weights and AdamW state the window left). What that call returns is what
# is compared: its loss, the gradient it applied (AdamW's first moment is m'
# = b1 m + (1 - b1) g, so g = (m' - b1 m) / (1 - b1) to float32 rounding),
# the weights it left, the picks it counted and the bias it moved. The system
# computes in bfloat16 (8 bits of mantissa) with float32 accumulation; the
# router, the scan's dt, cumulative log-decay, decay matrix and chunk states,
# the gate and the grouped norm's statistic, the softmax statistic, the
# relu^2, the norms' statistics and the loss are float32. Each limit lies
# between two readings on the v5e (my chip runs, PR 55; PERF.md section 6
# has the seeds): the largest a sound run gave and what a program wrong on
# purpose gave, with room on both sides.
#
# (A) AGAINST THE FLOAT32 REFERENCE (reference.py at "highest", handed the
# weights the step STARTED from under their HF names, the same share, and
# the system's OWN expert picks: an expert whose score is within rounding of
# a token's 6th flips between a bfloat16-operand system and a float32
# reference, and a flipped pick moves a token's path by a step no tolerance
# on values can cover; (B) holds the picks).
HIDDEN_REL_RMS_TOL = 3e-2    # the residual stream after EACH of the nine
                             # sublayers (the model's forward on the weights
                             # the step started from), of its RMS, each held
                             # to its own reading: 0.58-0.59 % after layer 0,
                             # rising to 0.99-1.22 % after layer 8, over 16
                             # runs: bfloat16 matmuls alone (with the picks
                             # given no flipped pick adds). Wrong on purpose
                             # (reference.py with ONE thing patched in its
                             # text, so that the system reads as wrong by
                             # the same distance; one call, seed 3000000041):
                             # the norm over all 4,096 channels 19.5 % (11 %
                             # after layer 0), the scale 2.5 left out 6.0 %,
                             # plain relu 32 %, SiLU 48 %, the shared expert
                             # left out 79 %. NOT seen by the stream, and
                             # held by the gradients below: a gate added
                             # 1.8 %, a rotation of q and k 1.4 %, a head on
                             # the next group's B and C and the state
                             # dropped at each chunk's start 1.22 % = the
                             # sound reading (at the program's initial
                             # weights, taps of std 0.02, the recurrence is a
                             # thousandth of D x)
LOSS_ABS_TOL = 1e-3          # the loss the STEP returned, of 7.1-7.3: 6e-6
                             # to 3.2e-4 over 16 runs. Wrong on purpose: a
                             # gate added 2.8e-3, the norm over all channels
                             # 2.2e-3, the scale left out 1.2e-2, relu 0.29,
                             # SiLU 1.2, no shared expert 2.3; the rotation
                             # (4.2e-4) and the two of the recurrence (1.0e-4)
                             # stay under it: the gradients tell
# the gradient the step applied, every token of it, of the reference's RMS,
# the worst layer of a family, in five classes: the final norm's scale sees
# the head's backward pass alone; a matrix outside the routed experts is a
# sum over 8,192 rows; a held expert's matrices see only the ~384 rows routed
# to them; a router's gradient is a difference of near equal terms over 128
# scores a token; a vector's (a norm's scale, the convolution's taps and
# bias, A_log, dt_bias, D, the grouped norm's scale) a sum of cancelling terms
# over every position. Sound runs (16 runs, a seed each, at step 55-75) -> the smallest
# reading of a wrong-on-purpose reference that the class has to catch ->
# limit:
HEAD_GRAD_REL_RMS_TOL = 0.03       # 0.58-1.1 % -> 8.0 % (a gate added)
MATRIX_GRAD_REL_RMS_TOL = 0.06     # 1.7-1.8 % (Ws1 the largest) -> 7.5 % (a
                                   # gate); the rotation 169 % (Wq), 121 % (Wk)
EXPERT_GRAD_REL_RMS_TOL = 0.2      # 5.9-11.2 % -> 33 % (the norm over all
                                   # channels); a gate added reads 17.6 %
ROUTER_GRAD_REL_RMS_TOL = 0.4      # 10.9-16.5 % -> 81 % (relu); a gate 21 %
VECTOR_GRAD_REL_RMS_TOL = 0.12     # 1.4-4.3 % (A_log, dt_bias the largest)
                                   # -> 21.5 % (dt_bias, the state dropped at
                                   # each chunk's start; A_log 102 %); a head
                                   # on the next group's B and C 225 % (A_log)
UPDATE_REL_ERR_TOL = 0.3     # the step's change of the weights compared,
                             # |(p' - p) - (AdamW(p, m, v, g_ref) - p)| over
                             # |AdamW(p, m, v, g_ref) - p|, the reference's
                             # float64 AdamW (reference.adamw_after_step,
                             # rounded to the float32 a weight is kept in) on
                             # the state the step started from and the
                             # REFERENCE's gradient, the worst family:
                             # 0.06-0.6 % the matrices, 2.3-3.0 % the
                             # experts, 1.4-2.1 % the norms, 2.5-8.6 % A_log
                             # and dt_bias (float32's step at |p| = 4-7 is a
                             # sixth of lr), 7.1-7.9 % the embedding (rows
                             # seen once). A state left unchanged reads 1 in
                             # every family. Between the largest reading and
                             # 1, more room above it
# (B) THE PICKS, against numpy float64 scores on the router's OWN input rows
# (bfloat16 as the system rounded them: `moe_routing_stats` holds them behind
# an optimization barrier), float32 weights and the bias, every token of the
# sample, every expert layer: an expert the system picked and float64 would
# not must lie within float32 rounding of the token's 6th score + bias, |z64
# - theta64| <= NEAR_PICK_REL x the token's largest |z64| (2,688 products at
# "highest", a float32 sigmoid and top-k). Measured -> bound: 0-2 of 196,608
# picks differ a run (1.0e-5) at up to 4.8e-7 over 11 runs; laguna-xs.2's
# limits. WITHOUT the barrier
# the compiler fed the router's matmul the norm's unrounded float32 output
# (excess precision: an "mlp" layer's norm reads the scan's carry as it
# stands and fuses into the matmul) and 706-762 picks (3.7e-3) differed at
# up to 4.4e-3, bfloat16's step: five runs read `correct` false on that
# alone (PERF.md section 6)
PICKS_DIFFER_MAX_SHARE = 1e-4
NEAR_PICK_REL = 2e-5
# And the picks the STEP counted an expert (the bias's first AdamW slot)
# against the counts of the routing pass's picks, half the sum of the
# counts' differences over the picks: the two programs round the routers'
# INPUT rows apart (another fusion of the same bfloat16 operations), so
# picks near a token's 6th score flip: 0.17-0.20 % over 16 runs
STEP_PICKS_MOVED_MAX_SHARE = 1e-2
# (C) THE MIXER'S FLOAT32 PARTS, against numpy float64 on the system's OWN
# inputs (transformer.ssm_scan_terms and ssm_gate_terms, layer 0): what holds
# float32 to float32 whatever the bfloat16 operands did. granite-4.0-h-micro's
# limits for the first four (its wrong-on-purpose readings: dt in bfloat16
# 3.96e-3, the log-decay cumulated in bfloat16 1.37e-3, the states summed in
# bfloat16 1.66e-3); here the entering states are NOT zero (RMS 3.4-4.3e-5
# beside the chunks' own: the dt initialisation, config.json `assumed`), so
# the recurrence over chunk states is held too
OWN_DT_REL_TOL = 1e-3            # 2.63-2.64e-4 (the TPU's float32 exp, log1p)
OWN_LOG_DECAY_REL_TOL = 1e-5     # 1.2-1.6e-6
OWN_STATE_REL_RMS_TOL = 3e-5     # own states 3.1-3.6e-8; entering 9e-9 to
                                 # 8.1e-7 over 16 runs (a float32 recurrence
                                 # over 64 chunk states of RMS 4e-5 against
                                 # float64: it swings with the seed), so NOT
                                 # Granite's 1e-6 (its entering states are
                                 # 0): between 8.1e-7 and the 1.66e-3 of
                                 # states summed and carried in bfloat16
OWN_GATE_NORM_REL_RMS_TOL = 1e-5     # the grouped norm of the system's own
                                     # y silu(z), every GATE_STRIDE-th
                                     # position: 6.5-6.6e-8; the SYSTEM's
                                     # statistic over all 4,096 channels
                                     # reads 0.11 (and nothing else of the
                                     # check can see the system's own norm
                                     # apart from the stream's 19.5 %)
GATE_STRIDE = 8
COMPARED_ENTRIES = 1 << 22
# the leaves whose gradients and updates are compared, by family: every layer
# for the vectors, the routers and attention's matrices (the worst), the
# first layer of its letter for the large matrices, both tables
VECTOR_GRADS = ("norm", "conv_w", "conv_b", "A_log", "dt_bias", "D",
                "ssm_norm")
MATRIX_GRADS = ("w_in", "w_out", "wq", "wk", "wv", "wo", "shared_w1",
                "shared_w2", "embed", "head")
EXPERT_GRADS = ("expert_w1", "expert_w2")
GRAD_TOLS = {"lnf_scale": HEAD_GRAD_REL_RMS_TOL,
             "router": ROUTER_GRAD_REL_RMS_TOL,
             **dict.fromkeys(MATRIX_GRADS, MATRIX_GRAD_REL_RMS_TOL),
             **dict.fromkeys(EXPERT_GRADS, EXPERT_GRAD_REL_RMS_TOL),
             **dict.fromkeys(VECTOR_GRADS, VECTOR_GRAD_REL_RMS_TOL)}


def build(config, traffic, seed, devices, batches, spans):
    try:
        from hetu_tpu.models import hf_nemotron_h    # noqa: F401
    except ImportError as e:
        # a program from before PR 55 (the parent this cell is tried on
        # first): refused in one line, as a cell whose files are missing
        from benchmark.harness.manifest import ManifestError
        raise ManifestError(
            f"nemotron-twotower-30b-a3b: this program has no loader for it "
            f"({e}): no layer of one sublayer, no relu2 experts, no gated "
            "norm by group") from e
    return NemotronHJob(config, traffic, seed, devices, batches, spans)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def _sampled(a):
    """A leaf as compared: every row of a vector or a small matrix, of a
    larger one every n-th row of its first axis, n the least that leaves at
    most COMPARED_ENTRIES entries (the float64 comparison on the host costs
    ~0.5 s a million entries: every row of the 252M entries named would be
    two minutes of a run). An entry of a gradient is still a sum over every
    token of the sample."""
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


def _own_terms_f64(t, gate, groups, eps):
    """Part (C) in numpy float64 from the system's own inputs -> the errors
    of its float32 parts: dt against softplus(raw + bias); the cumulative
    log-decay against the cumulated dt * A of ITS dt; the chunks' own states
    against sum_s B_s (x) xd_s of ITS bfloat16 operands; the entering states
    against the recurrence over ITS own states and chunk decays; the gated
    norm against the statistic of each of `groups` runs of ITS y silu(z)."""
    f64 = lambda x: np.asarray(x).astype(np.float64)
    dt = np.logaddexp(0.0, f64(t["dt_raw"]) + f64(t["dt_bias"]))
    log_decay = f64(t["log_decay"])                 # (B, c, Q, G, R)
    B_, c, Q, G, R = log_decay.shape
    step = (f64(t["dt"]) * -np.exp(f64(t["A_log"]))).reshape(B_, c, Q, G, R)
    Bm, xd = f64(t["B"]), f64(t["xd"])
    local = np.einsum("bcsgn,bcsgk->bcgkn", Bm,
                      xd.reshape(B_, c, Q, G, -1), optimize=True)
    got_local = f64(t["local"])
    S, entering = np.zeros_like(got_local[:, 0]), []
    for i in range(c):
        entering.append(S)
        S = np.exp(log_decay[:, i, -1])[..., None, None] * S + got_local[:, i]
    gated = f64(gate["gated"])
    by_group = gated.reshape(gated.shape[:-1] + (groups, -1))
    normed = (by_group / np.sqrt(np.mean(by_group ** 2, -1, keepdims=True)
                                 + eps)).reshape(gated.shape) * f64(
        gate["scale"])
    return {
        "own_dt_rel_err": float(np.max(np.abs(f64(t["dt"]) - dt) / dt)),
        "own_log_decay_rel_rms_err": _rel_rms(log_decay, np.cumsum(step, 2)),
        "own_local_state_rel_rms_err": _rel_rms(
            got_local.reshape(local.shape), local),
        "own_entering_state_rel_rms_err": _rel_rms(
            f64(t["entering"]), np.stack(entering, 1)),
        "own_entering_state_rms": float(np.sqrt(np.mean(
            f64(t["entering"]) ** 2))),
        "own_gate_norm_rel_rms_err": _rel_rms(gate["normed"], normed)}


def _loads(picks, first, n_held):
    """(layers, E) picks an expert -> ([the fullest expert's load over the
    mean, a layer], the share of all picks on the experts held, in %)."""
    picks = np.asarray(picks, np.float64)
    return ((picks.max(-1) / picks.mean(-1)).tolist(),
            100.0 * picks[:, first:first + n_held].sum() / picks.sum())


class NemotronHJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_nemotron_h, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_nemotron_h.config_from_hf(
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
        # slot; (expert layers, 128). In a traced run a copy is kept a step
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
        self._moe = None

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
        from benchmark.reduce import nemotron_h
        out = {"flops_per_item": nemotron_h.flops_per_token(
            self.config, self.traffic["seq_len"])}
        if self._moe is not None:
            out["moe"] = self._moe
        if self._step_picks:
            # the traced window's steps come first after the warm-up
            warm = self.traffic.get("warmup_steps", 3)
            steps = self._step_picks[warm:warm + self.traffic["trace_steps"]]
            out["traced_picks"] = [np.asarray(p).tolist() for p in steps]
        return out

    def _hf_names(self):
        """{a name of GRAD_TOLS: the groups of HF names whose gradients it
        covers}: a group is one leaf of one layer (the held experts'
        matrices of a layer are one); the worst group is reported."""
        from hetu_tpu.models import hf_nemotron_h as hn
        cfg = self.cfg
        letters = hn.pattern_of(self.config)
        at = lambda letter: [i for i, x in enumerate(letters) if x == letter]
        mamba, attn, moe = at("M"), at("*"), at("E")
        first = cfg.router.first_held
        experts = lambda i, w: [hn.expert_name(i, first + e, w)
                                for e in range(cfg.n_experts)]
        part = {**hn.MAMBA_VECTORS, "conv_w": hn.CONV_W}
        names = {n: [[hn.hf_name(i, part[n])] for i in mamba] for n in part}
        names.update({"w" + x: [[hn.hf_name(i, p)] for i in attn]
                      for x, p in zip("qkvo", hn.QKV + (hn.WO,))})
        names.update(
            norm=[[hn.hf_name(i, hn.NORM)] for i in range(len(letters))],
            lnf_scale=[[hn.FINAL_NORM]], embed=[[hn.EMBED]], head=[[hn.HEAD]],
            w_in=[[hn.hf_name(mamba[0], hn.MAMBA_LINEARS["w_in"])]],
            w_out=[[hn.hf_name(mamba[0], hn.MAMBA_LINEARS["w_out"])]],
            router=[[hn.hf_name(i, hn.ROUTER)] for i in moe],
            shared_w1=[[hn.shared_name(moe[0], "w1")]],
            shared_w2=[[hn.shared_name(moe[0], "w2")]],
            expert_w1=[experts(moe[0], "w1")],
            expert_w2=[experts(moe[0], "w2")])
        return {n: names[n] for n in GRAD_TOLS}

    def check(self, reference):
        """One more call of the timed step on the correctness sample
        (`_observe`), and what it returned against the float32 reference
        GIVEN the system's own picks (`_compare`): its loss, the gradient it
        applied, the weights it left (the reference's AdamW), the bias it
        moved (the reference's rule); the residual stream after each of the
        nine sublayers. Part (B): the picks against float64 scores on the
        routers' own input rows. Part (C): the scan's and the gated norm's
        float32 parts against float64 on the system's own inputs."""
        return self._compare(reference, self._observe())

    def _observe(self):
        """The system's side of the check, nothing of the reference: what
        the timed step returned on the sample, and what the program's own
        pure functions (`_through_run`, `moe_routing_stats`,
        `ssm_scan_terms`, `ssm_gate_terms`) make of the weights it started
        from."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_nemotron_h, transformer as tfm
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
                sd = hf_nemotron_h.state_dict_from_params(tree, cfg)
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
        # the job is over: its 8 GB are the check's. The weights the step
        # started from, bit for bit, for every program below
        self.opt = self.params = None
        params = jax.device_put(start, self.device)
        del start
        t1 = time.perf_counter()

        # tokens are arguments, not constants of the programs: every seed
        # then reads the same entries of the compile cache. Two forward
        # programs (the stream with the routing; layer 0's mixer terms), run
        # one after another, on a chip the job has just left
        def hidden_and_routing(params, tokens):
            h, after = tfm.embed_tokens(params, tokens, cfg), []
            for (kind, _), blocks in zip(
                    tfm.layer_runs(cfg),
                    tfm.run_blocks(cfg, params["blocks"])):
                h = tfm._through_run(h, blocks, cfg, kind)
                after.append(h.astype(jnp.float32))
            return after, tfm.moe_routing_stats(params, tokens, cfg,
                                                terms=True)

        def gate(params, tokens):
            terms = tfm.ssm_gate_terms(params, tokens, cfg)
            return {"gated": terms["gated"][:, ::GATE_STRIDE],
                    "normed": terms["normed"][:, ::GATE_STRIDE],
                    "scale": terms["scale"]}

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
            jax.device_get(jax.jit(lambda p, t: tfm.ssm_scan_terms(
                p, t, cfg))(params, tokens)),
            jax.device_get(jax.jit(gate)(params, tokens)),
            cfg.ssm.norm_groups, cfg.ln_eps)
        sd = hf_nemotron_h.state_dict_from_params(params, cfg)
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
        reference jits its layers and head itself) -> the check's result."""
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
        grad_err, update_err = {}, {}
        for n, groups in hf_names.items():
            grad_err[n] = update_err[n] = 0.0
            for group in groups:
                p, m, v, g = (pooled(tree, group) for tree in (
                    before["p"], before["m"], before["v"], want_grads))
                got_g = (pooled(after["m"], group) - b1 * m) / (1.0 - b1)
                # rounded to the float32 a weight is kept in: at |p| = 4-7
                # (dt_bias, A_log) float32's step is 4.8e-7, a sixth of lr,
                # and the unrounded float64 result reads the ROUNDING as an
                # error of the update (57 % on dt_bias at a toy size)
                want_p = reference.adamw_after_step(
                    p, m, v, g, seen["step_no"],
                    config["assumed"]["learning_rate"], adamw).astype(
                        np.float32).astype(np.float64)
                grad_err[n] = max(grad_err[n], _rel_rms(got_g, g))
                update_err[n] = max(update_err[n], _rel_rms(
                    pooled(after["p"], group) - p, want_p - p))
        t4 = time.perf_counter()

        picks_checked, picks_differ, picks_worst = seen["picks"]
        step_loss = seen["step_loss"]
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
               **seen["own_terms"],
               "step": seen["step_no"],
               "grad_rel_rms_err": grad_err,
               "update_rel_err": update_err,
               "by_sync": [
                   dict(zip(("steps", "load_max_over_mean", "held_pick_pct"),
                            (i,) + _loads(p, r.first_held, cfg.n_experts)))
                   for i, p in self._sync_picks],
               "sample": list(tokens.shape),
               "seconds": {**seen["seconds"],
                           "reference": t3 - t2,
                           "host_comparison": t4 - t3}}
        out["ok"] = bool(
            np.isfinite(out["loss"])
            and out["loss_abs_err"] <= LOSS_ABS_TOL
            and len(hidden_err) == cfg.n_layers
            and max(hidden_err.values()) <= HIDDEN_REL_RMS_TOL
            and out["picks_differ_share"] <= PICKS_DIFFER_MAX_SHARE
            and out["picks_differ_worst_distance"] <= NEAR_PICK_REL
            and out["step_picks_moved_share"] <= STEP_PICKS_MOVED_MAX_SHARE
            and out["dropped_picks"] == 0
            and out["bias_entries_unexplained"] == 0
            and out["own_dt_rel_err"] <= OWN_DT_REL_TOL
            and out["own_log_decay_rel_rms_err"] <= OWN_LOG_DECAY_REL_TOL
            and max(out["own_local_state_rel_rms_err"],
                    out["own_entering_state_rel_rms_err"]
                    ) <= OWN_STATE_REL_RMS_TOL
            and out["own_entering_state_rms"] > 0.0
            and out["own_gate_norm_rel_rms_err"] <= OWN_GATE_NORM_REL_RMS_TOL
            and set(grad_err) == set(GRAD_TOLS)
            and all(err <= GRAD_TOLS[n] for n, err in grad_err.items())
            and max(update_err.values()) <= UPDATE_REL_ERR_TOL)
        return out

    def close(self):
        pass
