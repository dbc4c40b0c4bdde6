"""laguna-xs.2 as a user's job script builds it: the published config.json
(cut to one chip's share, config.json `reduced`) through
`hf_laguna.config_from_hf`, weights from the program's own initialiser,
`transformer.make_train_step` (next-token loss on the untied head, AdamW in
the step, the routers' selection bias by its sign rule after it). Only
architecture, shapes, optimizer, the bias's rate and compute dtype are
stated; attention implementation, fused cross-entropy, recomputation, the
grouped matmul and kernel mode stay the program's defaults.
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
# rotary tables and the rotation, the softmax statistic, the gate's sigmoid
# and product, the router, the norms' statistics and the loss are float32.
# Each limit lies between two readings on the v5e (my chip runs, PR 49;
# PERF.md section 6 has the seeds): the largest a sound run gave and what a
# program wrong on purpose gave, with room on both sides.
#
# (A) AGAINST THE FLOAT32 REFERENCE (reference.py at "highest", handed the
# weights the step STARTED from under their HF names, the same share, and
# the system's OWN expert picks: an expert whose score is within rounding of
# a token's 8th flips between a bfloat16-operand system and a float32
# reference, and a flipped pick moves a token's path by a step no tolerance
# on values can cover; (B) holds the picks).
HIDDEN_REL_RMS_TOL = 3e-2    # the residual stream after each RUN of layers
                             # (the model's forward on the weights the step
                             # started from), of its RMS, each run held to
                             # its own reading: layer 0 (full + dense)
                             # 0.85-0.89 %, layers 1-3 (the window run)
                             # 0.75-0.82 %, layer 4 (full + experts)
                             # 0.80-0.87 %: bfloat16 matmuls alone (with the
                             # picks given no flipped pick adds). YaRN's
                             # ramp left out reads 52.6 %, rotary on all of
                             # a full head's columns 86.9 %, the gate left
                             # out 56-129 %
LOSS_ABS_TOL = 2e-3          # the loss the STEP returned, of 6.6: 2.0e-5 to
                             # 2.6e-4 over 8 runs (7.8e-4 after 14 steps
                             # instead of 40; the gate left out 0.35);
                             # kanana-2-30b-a3b's and lfm2-8b-a1b's limit
# the gradient the step applied, every token of it, of the reference's RMS,
# the worst layer of a kind, in five classes: the final norm's scale sees the
# head's backward pass alone; a matrix outside the expert block is a sum over
# 16,384 rows; a held expert's matrices see only the ~512 rows routed to
# them; a router's gradient is a difference of near equal terms over 256
# scores a token; a vector's a sum of cancelling terms over every position.
# Sound runs (8 seeds at step 40 or 45) -> the CONTROL, the reference with
# the second half of the sequence left out of its loss (half of the
# gradient's terms missing), which has to fail each -> limit:
HEAD_GRAD_REL_RMS_TOL = 0.05       # 0.7-1.4 % -> 112 %
MATRIX_GRAD_REL_RMS_TOL = 0.1      # 1.2-3.0 % (Wq, Wk the largest) -> 111-117 %
EXPERT_GRAD_REL_RMS_TOL = 0.3      # 5.7-9.7 % -> 112-113 %
ROUTER_GRAD_REL_RMS_TOL = 0.5      # 15.5-29.1 % -> 161 %
VECTOR_GRAD_REL_RMS_TOL = 0.1      # 1.4-3.2 % -> 111-114 %
UPDATE_REL_ERR_TOL = 0.3     # the step's change of the weights compared,
                             # |(p' - p) - (AdamW(p, m, v, g_ref) - p)| over
                             # |AdamW(p, m, v, g_ref) - p|, the reference's
                             # float64 AdamW (reference.adamw_after_step) on
                             # the state the step started from and the
                             # REFERENCE's gradient, the worst class:
                             # matrices 0.3-0.8 %, experts 2.2-3.0 %, norm
                             # scales 2.4-6.1 % (float32's step at 1.0 is 4 %
                             # of lr), routers 7.0-9.5 %. A state left
                             # unchanged reads 1 in every class (and AdamW
                             # without its bias correction 4.8: CPU, a toy
                             # size); the control above 15-35 %. Between the
                             # largest reading and 1, more room above it
# (B) THE PICKS, against numpy float64 scores on the router's OWN input rows
# (bfloat16 as the system rounded them), float32 weights and the bias, every
# token of the sample, every expert layer: an expert the system picked and
# float64 would not must lie within float32 rounding of the token's 8th
# score + bias, |z64 - theta64| <= NEAR_PICK_REL x the token's largest |z64|
# (2,048 products at "highest", a float32 sigmoid and top-k). Measured ->
# bound: 0-4 of 524,288 picks differ a run (7.6e-6) at up to 5.3e-7; with the
# router's logits and scores rounded to bfloat16 9,898 differ (1.9e-2) at up
# to 4.5e-3, and nothing else of the check moves (the residual stream reads
# 0.9-1.8 %): only this part can tell.
PICKS_DIFFER_MAX_SHARE = 1e-4
NEAR_PICK_REL = 2e-5
# And the picks the STEP counted an expert (the bias's first AdamW slot)
# against the counts of the routing pass's picks, half the sum of the
# counts' differences over the picks: the two programs round the routers'
# INPUT rows apart (another fusion of the same bfloat16 operations), so
# picks near a token's 8th score flip: 0.22-0.26 % over 13 runs; the router
# in bfloat16 moves 1.9 % against float64 on the same rows
STEP_PICKS_MOVED_MAX_SHARE = 1e-2
# (C) THE FLOAT32 PARTS AND THE KEPT SETS, against numpy float64 on the
# system's OWN inputs (transformer.attention_terms: the first window layer's
# and the first full layer's q and k before and after the rotation, and what
# the layer's own mixer, the function the step's block calls, makes of them):
# what holds whatever the bfloat16 operands did, and what a window off by one
# key, a rotary table of another form or a rotation of other columns each
# breaks BY ITS OWN TERM. The residual stream cannot tell a window off by
# one: the stream after the window run reads 0.97 % for 0.80.
OWN_OUT_REL_RMS_TOL = 2e-2   # a layer's mixer output (attention over exactly
                             # the keys t - 512 < s <= t, or every s <= t;
                             # the gate; Wo) on OUT_ROWS rows at the
                             # sequence's start and at its end, of its RMS,
                             # against float64 on the same bfloat16 q, k, v,
                             # x, Wg and Wo: 0.268-0.274 % the window layer,
                             # 0.284-0.291 % the full one (the kernels'
                             # bfloat16 probabilities and the three roundings
                             # to bfloat16 on the way); the gate left out
                             # 101 % and 102 %
OWN_WINDOW_EDGE_TOL = 0.1    # of what one key more (s >= t - 512) or one
                             # fewer would add to the window layer's output
                             # (float64), the share found in the system's
                             # output: 0.0012-0.0036 over 6 seeds, 1.0001
                             # for a model that hands its kernels 513 keys.
                             # The output's error alone cannot tell (0.27 %
                             # sound, 0.38-0.40 % with 513 keys: one key of
                             # 512 whose value is much like the others')
OWN_ROPE_REL_RMS_TOL = 1e-2  # the rotated q and k of the first window layer
                             # (all 128 columns, theta 1e4: 1.66e-3) and of
                             # the first full layer (64 columns by YaRN's
                             # table, times the attention factor; 64 passing:
                             # 1.35-1.36e-3), of their RMS, against float64
                             # on the unrotated bfloat16 columns: the
                             # result's own rounding to bfloat16. The full
                             # layer's without the attention factor reads
                             # 0.240, by the plain table (no ramp) 0.847,
                             # with all 128 columns turned 1.230
OUT_ROWS = 128               # rows [0, 128) (windows cut by the start) and
                             # the sample's last 128 (full windows)
ROPE_STRIDE = 8              # the rotation is held on every eighth position
# (D) THE PAIRS THE WINDOW LAYERS' KERNELS COMPUTE, measured on the chip
# through the first window layer's own mixer (transformer.attention_visits:
# a chunk of keys made NaN at a time, the rows that come out NaN counted)
# against the plan of the kernels' loop bounds (transformer.attention_pairs)
# and against MAX_COMPUTED_OVER_KEPT: whole 512 x 512 tiles read 199.994 %,
# a kernel that walks every causal tile under the mask 1,676 %
VISIT_CHUNK = 128            # divides every key tile the kernels choose
MAX_COMPUTED_OVER_KEPT = 3.0
# the leaves whose gradients and updates are compared, by the trunk's names:
# every layer for the vectors, the attention's matrices (both kinds, the
# worst) and the routers, one matrix a kind elsewhere. NOT the embedding
# (sparse rows) nor the 26M-entry head
VECTOR_GRADS = ("ln1_scale", "ln2_scale")
MATRIX_GRADS = ("wq", "wk", "wv", "wo", "wg", "dense_w1_layer0",
                "shared_w1", "shared_w2")
EXPERT_GRADS = ("expert_w1_layer1", "expert_w2_layer1")
GRAD_TOLS = {"lnf_scale": HEAD_GRAD_REL_RMS_TOL,
             "router": ROUTER_GRAD_REL_RMS_TOL,
             **dict.fromkeys(MATRIX_GRADS, MATRIX_GRAD_REL_RMS_TOL),
             **dict.fromkeys(EXPERT_GRADS, EXPERT_GRAD_REL_RMS_TOL),
             **dict.fromkeys(VECTOR_GRADS, VECTOR_GRAD_REL_RMS_TOL)}


def build(config, traffic, seed, devices, batches, spans):
    try:
        from hetu_tpu.models import hf_laguna    # noqa: F401
    except ImportError as e:
        # a program from before PR 49 (the parent this cell is tried on
        # first): refused in one line, as a cell whose files are missing
        from benchmark.harness.manifest import ManifestError
        raise ManifestError(
            f"laguna-xs.2: this program has no loader for it ({e}): no "
            "window kind, no per-kind head count, no YaRN table") from e
    return LagunaJob(config, traffic, seed, devices, batches, spans)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


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


def _rotated_f64(reference, raw, r, hd):
    """(T / ROPE_STRIDE, heads * hd) unrotated columns at positions 0,
    ROPE_STRIDE, ... -> float64 rotate-half rotation of each head's first
    `partial_rotary_factor` x hd columns by `reference.yarn_table`'s float64
    frequencies, the others passing."""
    T = raw.shape[0]
    x = np.asarray(raw, np.float64).reshape(T, -1, hd)
    rot = int(round(r.get("partial_rotary_factor", 1.0) * hd))
    inv, factor = reference.yarn_table(r, rot)
    angle = ROPE_STRIDE * np.arange(T, dtype=np.float64)[:, None, None] * inv
    cos, sin = np.cos(angle) * factor, np.sin(angle) * factor
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin,
                           x[..., rot:]], -1).reshape(T, -1)


def _mixer_out_f64(terms, hd, W, T):
    """A layer's mixer in float64 on the system's own operands, the rows
    `_row_blocks` names: softmax(q k^T / sqrt(hd)) v over the keys s <= t
    (and s > t - W under a window W), head h on k/v head h // (heads / kv
    heads), times the head's gate sigmoid(x Wg), through Wo -> (blocks,
    OUT_ROWS, D)."""
    f64 = lambda a: np.asarray(a, np.float64)
    wg, wo = f64(terms["wg"]), f64(terms["wo"])
    k_all, v_all = (f64(terms[n]).reshape(T, -1, hd)
                    for n in ("k_own", "v_own"))
    G = k_all.shape[1]
    out = []
    for (lo, hi), q, x in zip(_row_blocks(T), f64(terms["q_rows"]),
                              f64(terms["x_rows"])):
        first = 0 if W is None else max(lo - W + 1, 0)
        t = np.arange(lo, hi)[:, None]
        pos = np.arange(first, hi)[None, :]
        keep = pos <= t if W is None else (pos <= t) & (pos > t - W)
        q = q.reshape(hi - lo, G, -1, hd)                   # (R, G, group, hd)
        group = q.shape[2]
        o = np.empty(q.shape)
        for g in range(G):      # one matrix product a k/v head, its group's
            k, v = k_all[first:hi, g], v_all[first:hi, g]   # rows together
            s = np.where(np.repeat(keep, group, 0), q[:, g].reshape(
                -1, hd) @ k.T / np.sqrt(hd), -np.inf)       # (R group, S)
            a = np.exp(s - s.max(-1, keepdims=True))
            o[:, g] = (a / a.sum(-1, keepdims=True) @ v).reshape(
                -1, group, hd)
        gate = 1.0 / (1.0 + np.exp(-(x @ wg)))              # (R, heads)
        out.append((o.reshape(hi - lo, -1, hd) * gate[..., None]).reshape(
            hi - lo, -1) @ wo)
    return np.stack(out)


def _row_blocks(T):
    rows = min(OUT_ROWS, T)
    return ((0, rows), (T - rows, T))


def _own_terms_f64(reference, config, window, full, T):
    """Part (C) in numpy float64 from the system's own inputs -> the errors
    of its float32 parts and kept sets: the rotation of q and k, each
    kind's, each kind's mixer output over exactly the keys it keeps, and the
    window's edge: how much of what ONE KEY MORE (s >= t - W) or one fewer
    would add to the window layer's output is in the system's, the projection
    of its difference from the float64 output onto the difference that key
    makes (0 for exactly the window's keys, 1 for a window off by one)."""
    hd, W = config["head_dim"], config["sliding_window"]
    rope = config["rope_parameters"]
    out = {}
    for name, terms, r, w in (
            ("window", window, rope["sliding_attention"], W),
            ("full", full, rope["full_attention"], None)):
        out[f"own_rope_rel_rms_err_{name}"] = max(
            _rel_rms(terms[n][0], _rotated_f64(reference, terms[n + "_raw"][0],
                                               r, hd)) for n in "qk")
        want = _mixer_out_f64(terms, hd, w, T)
        out[f"own_out_rel_rms_err_{name}"] = _rel_rms(terms["out_rows"], want)
        if w is not None:
            off = np.asarray(terms["out_rows"], np.float64) - want
            by_a_key = [_mixer_out_f64(terms, hd, w + more, T) - want
                        for more in (1, -1)]
            out["own_window_edge_share"] = max(
                abs(float((off * d).sum() / max((d * d).sum(), 1e-300)))
                for d in by_a_key)
    return out


def _loads(picks, first, n_held):
    """(layers, E) picks an expert -> ([the fullest expert's load over the
    mean, a layer], the share of all picks on the experts held, in %)."""
    picks = np.asarray(picks, np.float64)
    return ((picks.max(-1) / picks.mean(-1)).tolist(),
            100.0 * picks[:, first:first + n_held].sum() / picks.sum())


class LagunaJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_laguna, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_laguna.config_from_hf(
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
        self._moe = None
        self._computed = None      # the check's: pairs a window layer computed

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

    def _attn_pairs(self):
        """The program's pair counter at the step's own shapes: the plan of
        the kernels' loop bounds and, once the check has measured them on
        the device, the pairs the window layers' kernels COMPUTED."""
        from hetu_tpu.models import transformer as tfm
        pairs = tfm.attention_pairs(self.cfg, self.traffic["seq_len"])
        for kind, stats in pairs.items():
            stats["planned"] = stats["computed"]
            if kind == "window" and self._computed is not None:
                stats["computed"] = self._computed
            stats["kept_pct"] = 100.0 * stats["kept"] / stats["causal"]
            stats["computed_pct"] = 100.0 * stats["computed"] / stats["causal"]
        return pairs

    def counters(self):
        from benchmark.reduce import swa
        out = {"flops_per_item": swa.laguna_train_flops_per_token(
            self.config, self.traffic["seq_len"]),
            "attn_pairs": self._attn_pairs()}
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
        from hetu_tpu.models import hf_laguna as hl, transformer as tfm
        cfg = self.cfg
        kinds = tfm.layer_kinds(cfg)
        every = range(len(kinds))
        moe = [i for i, k in enumerate(kinds) if tfm.experts_of(cfg, k)]
        dense = [i for i, k in enumerate(kinds)
                 if not tfm.experts_of(cfg, k)]
        first = cfg.router.first_held
        experts = lambda i, w: [hl.expert_name(i, first + e, w)
                                for e in range(cfg.n_experts)]
        names = {n: [[hl.hf_name(i, part)] for i in every]
                 for n, part in {**hl.NORMS, **hl.ATTN_LINEARS}.items()}
        names.update({"w" + x: [[hl.hf_name(i, part)] for i in every]
                      for x, part in zip("qkv", hl.QKV)})
        names.update(
            lnf_scale=[["model.norm.weight"]],
            router=[[hl.hf_name(i, hl.ROUTER)] for i in moe],
            dense_w1_layer0=[[hl.hf_name(dense[0], "mlp." + hl.MLP["w1"])]],
            shared_w1=[[hl.shared_name(i, "w1")] for i in moe],
            shared_w2=[[hl.shared_name(i, "w2")] for i in moe],
            expert_w1_layer1=[experts(moe[0], "w1")],
            expert_w2_layer1=[experts(moe[0], "w2")])
        return {n: names[n] for n in GRAD_TOLS}

    def check(self, reference):
        """One more call of the timed step on the correctness sample, and
        what it returned against the float32 reference GIVEN the system's
        own picks: its loss, the gradient it applied, the weights it left
        (the reference's AdamW), the bias it moved (the reference's rule);
        the residual stream after each run of layers. Part (B): the picks
        against float64 scores on the routers' own input rows. Part (C): the
        rotation of q and k and the mixer's output, each kind's, against
        float64 on the system's own inputs. Part (D): the pairs the window
        layer's kernels compute, measured."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_laguna, transformer as tfm
        from benchmark.generators import lm_zipf

        cfg, config = self.cfg, self.config
        r, rate = cfg.router, cfg.router.bias_rate
        sample = jax.device_put(lm_zipf.generate(
            self.traffic, config, self.seed,
            sequences=self.traffic["check_sequences"])[0], self.device)
        tokens, targets = sample["tokens"], sample["targets"]
        T = tokens.shape[1]
        runs = tfm.run_layers(cfg)
        hf_names = self._hf_names()
        wanted = sorted(h for groups in hf_names.values()
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
                sd = hf_laguna.state_dict_from_params(tree, cfg)
                return {n: np.asarray(sd[n]) for n in wanted}

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
        # the job is over: its 8.3 GB are the check's. The weights the step
        # started from, bit for bit, for every program below
        self.opt = self.params = None
        params = jax.device_put(start, self.device)
        del start
        t1 = time.perf_counter()

        # tokens are arguments, not constants of the programs: every seed
        # then reads the same entries of the compile cache. One program a
        # question, run one after another: together their working sets would
        # stand beside each other on a chip the step nearly fills
        def hidden(params, tokens):
            h, after = tfm.embed_tokens(params, tokens, cfg), []
            for (kind, _), blocks in zip(
                    tfm.layer_runs(cfg),
                    tfm.run_blocks(cfg, params["blocks"])):
                h = tfm._through_run(h, blocks, cfg, kind)
                after.append(h.astype(jnp.float32))
            return after

        hd = cfg.head_dim
        blocks = _row_blocks(T)

        def own(params, tokens, mixer):
            terms = tfm.attention_terms(params, tokens, cfg, mixer)
            kv = terms["k_raw"].shape[-1] // hd
            # k and v as the kernels take them are broadcast to the query
            # heads: the first head of each group is the k/v head's own
            own_kv = lambda x: x[0].reshape(T, kv, -1, hd)[:, :, 0].reshape(
                T, kv * hd)
            k = own_kv(terms["k"])
            rows = lambda x: jnp.stack([x[0, lo:hi] for lo, hi in blocks])
            return {"q_raw": terms["q_raw"][:, ::ROPE_STRIDE],
                    "k_raw": terms["k_raw"][:, ::ROPE_STRIDE],
                    "q": terms["q"][:, ::ROPE_STRIDE],
                    "k": k[None, ::ROPE_STRIDE],
                    "x_rows": rows(terms["x"]), "q_rows": rows(terms["q"]),
                    "out_rows": rows(terms["out"]),
                    "k_own": k, "v_own": own_kv(terms["v"]),
                    "wg": terms["wg"].astype(cfg.dtype),
                    "wo": terms["wo"].astype(cfg.dtype)}

        own = jax.jit(own, static_argnums=2)
        routing = jax.jit(lambda p, t: tfm.moe_routing_stats(
            p, t, cfg, terms=True))
        chunk = min(VISIT_CHUNK, T)
        visits = jax.jit(lambda p, t: tfm.attention_visits(
            p, t[:1], cfg, "window", chunk))
        stream = jax.jit(hidden)(params, tokens)
        stats = jax.device_get(routing(params, tokens))
        bias = np.asarray(self._picks_of(params))
        router_w = np.concatenate(
            [np.asarray(b["router"])
             for b in tfm.run_blocks(cfg, params["blocks"])
             if "router" in b])
        picks_checked, picks_differ, picks_worst = _picks_f64(
            stats.pop("router_in"), router_w, bias, stats["experts"])
        del router_w
        own_terms = _own_terms_f64(
            reference, config, jax.device_get(own(params, tokens, "window")),
            jax.device_get(own(params, tokens, "attention")), T)
        self._computed = int(np.asarray(visits(params, tokens)).sum()) * chunk
        t2 = time.perf_counter()
        loads, held_pct = _loads(stats["picks"], r.first_held, cfg.n_experts)
        self._moe = {"picks": stats["picks"].tolist(),
                     "max_over_mean": loads,
                     "held": stats["held"].tolist(),
                     "dropped": int(stats["dropped"].sum()),
                     "entropy": stats["entropy"].tolist()}

        sd = hf_laguna.state_dict_from_params(params, cfg)
        del params             # the reference holds its own (HF) views now
        # part (A): eagerly, the reference jits its layers and head itself
        picks = list(jnp.asarray(stats["experts"]))
        want_loss, want = reference.loss_terms(sd, tokens, targets, config,
                                               picks=picks)
        hidden_err = {
            f"after_{kind}_run_{n}": _rel_rms(got, want["hidden"][layers[-1]])
            for n, ((kind, layers), got) in enumerate(zip(runs, stream))}
        want_loss = float(want_loss)
        want_counts = jax.device_get(want["counts"])
        del want, stream
        # the bias the step left: the system's rule on the picks the STEP
        # counted against the reference's rule on the picks handed to it. An
        # entry may differ only where the picks the step's own forward pass
        # counted moved it across the mean
        want_bias = reference.bias_after_step(bias, want_counts, rate)
        differs = np.abs(bias_moved - want_bias) > rate / 2
        moved = np.abs(step_counts - want_counts)
        near = np.abs(want_counts - want_counts.mean(-1, keepdims=True)
                      ) <= moved.sum(-1, keepdims=True)
        t3 = time.perf_counter()
        _, want_grads = jax.device_get(reference.grads_of(wanted)(
            sd, tokens, targets, config, picks=picks))
        del sd
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
                want_p = reference.adamw_after_step(
                    p, m, v, g, step_no, config["assumed"]["learning_rate"],
                    adamw)
                grad_err[n] = max(grad_err[n], _rel_rms(got_g, g))
                update_err[n] = max(update_err[n], _rel_rms(
                    pooled(after["p"], group) - p, want_p - p))
        t4 = time.perf_counter()

        # the pairs the kernels computed, measured, against the plan of their
        # loops' bounds; the plan's kept pairs against the closed form: a
        # window layer keeps sum_t min(t + 1, W) pairs of T (T + 1) / 2
        W = config["sliding_window"]
        pairs = self._attn_pairs()
        causal = T * (T + 1) // 2
        closed = {"window": sum(min(t + 1, W) for t in range(T)),
                  "attention": causal}
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
               **own_terms,
               "kept_pair_pct": {k: v["kept_pct"] for k, v in pairs.items()},
               "computed_pair_pct": {k: v["computed_pct"]
                                     for k, v in pairs.items()},
               "planned_window_pairs": pairs["window"]["planned"],
               "step": step_no,
               "grad_rel_rms_err": grad_err,
               "update_rel_err": update_err,
               "by_sync": [
                   dict(zip(("steps", "load_max_over_mean", "held_pick_pct"),
                            (i,) + _loads(p, r.first_held, cfg.n_experts)))
                   for i, p in self._sync_picks],
               "sample": list(tokens.shape),
               "seconds": {"step": t1 - t0, "system": t2 - t1,
                           "reference_forward": t3 - t2,
                           "reference_gradients": t4 - t3}}
        out["ok"] = bool(
            np.isfinite(out["loss"])
            and out["loss_abs_err"] <= LOSS_ABS_TOL
            and max(hidden_err.values()) <= HIDDEN_REL_RMS_TOL
            and out["picks_differ_share"] <= PICKS_DIFFER_MAX_SHARE
            and out["picks_differ_worst_distance"] <= NEAR_PICK_REL
            and out["step_picks_moved_share"] <= STEP_PICKS_MOVED_MAX_SHARE
            and out["dropped_picks"] == 0
            and out["bias_entries_unexplained"] == 0
            and max(out["own_out_rel_rms_err_window"],
                    out["own_out_rel_rms_err_full"]) <= OWN_OUT_REL_RMS_TOL
            and out["own_window_edge_share"] <= OWN_WINDOW_EDGE_TOL
            and max(out["own_rope_rel_rms_err_window"],
                    out["own_rope_rel_rms_err_full"]) <= OWN_ROPE_REL_RMS_TOL
            and all(pairs[k]["kept"] == closed[k]
                    and pairs[k]["causal"] == causal for k in pairs)
            and set(pairs) == set(closed)
            and pairs["window"]["computed"] == pairs["window"]["planned"]
            # off the chip the step takes the dot path: dense under a mask
            and (pairs["window"]["computed"] <= MAX_COMPUTED_OVER_KEPT
                 * closed["window"] or jax.default_backend() != "tpu")
            and set(grad_err) == set(GRAD_TOLS)
            and all(err <= GRAD_TOLS[n] for n, err in grad_err.items())
            and max(update_err.values()) <= UPDATE_REL_ERR_TOL)
        return out

    def close(self):
        pass
