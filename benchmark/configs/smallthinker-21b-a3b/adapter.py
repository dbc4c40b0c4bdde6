"""smallthinker-21b-a3b as a user's job script builds it: the published
config.json (cut to one chip's share, config.json `reduced`) through
`hf_smallthinker.config_from_hf`, weights from the program's own initialiser,
`transformer.make_train_step` (next-token loss on the untied head plus the
routers' balance and z losses, AdamW in the step). Only architecture, shapes,
optimizer and compute dtype are stated; attention implementation, fused
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
# = b1 m + (1 - b1) g, so g = (m' - b1 m) / (1 - b1) to float32 rounding)
# and the weights it left. The system computes in bfloat16 (8 bits of
# mantissa) with float32 accumulation; the router (its logits from the
# bfloat16 stream), the picks' softmax and the combine, the rotary tables and
# the rotation, the softmax statistic of the attention kernels, the ReGLU,
# the norms' statistics and the loss are float32. Each limit lies between two
# readings on the v5e at the cell as it stands (the embedding at std 1.0,
# the held block taken by rows, lr 3e-6; my chip runs, PR 63; PERF.md
# section 6 has the seeds): the largest of 22 SOUND runs (seeds
# 3000000201-...207, ...311-...317, ...411-...417 and ...501, the last
# eight of the final tree; the check runs at step 63-69, the loss fallen
# 10.9 -> 8.1-8.3; ...208's at step 16 lies inside them) and what a
# reference wrong on purpose gave (reference.py with ONE line patched in
# its text, so that the system reads as wrong by the same distance; one
# call, seed 3000000208, at step 16), with room on both sides. At std 1.0
# the embedding's own rounding leads the stream's error and RMSNorm_1(x)
# lies close to x (its RMS is ~1), so the GRADIENTS are what tells the
# router's input, and their limits lie close over the sound readings: at
# 25-30 % the router fed RMSNorm_1(x) reads as correct.
#
# (A) AGAINST THE FLOAT32 REFERENCE (reference.py at "highest", handed the
# weights the step STARTED from under their HF names, the same share, and
# the system's OWN expert picks: an expert whose logit is within rounding of
# a token's 6th flips between a bfloat16-operand system and a float32
# reference, and a flipped pick moves a token's path by a step no tolerance
# on values can cover; (B) holds the picks. The picks' WEIGHTS and both
# router losses are the reference's own, from ITS router on ITS stream).
# sound -> the smallest wrong reading the limit is there to catch -> limit:
HIDDEN_REL_RMS_TOL = 1e-2    # the residual stream after each RUN of layers
                             # (the model's forward on the weights the step
                             # started from), of its RMS, each run held to
                             # its own reading: layer 0 (global NoPE)
                             # 0.29 %, layers 1-3 (the window run)
                             # 0.42-0.49 %: the bfloat16 stream's rounding
                             # of an embedding of norm ~50. Wrong: SwiGLU
                             # for ReGLU 2.0 %, the softmax over all 64 left
                             # unnormalised 4.3 %, RoPE on layer 0 6.3 %,
                             # no RoPE on a window layer 9.7 %, the 48
                             # absent experts' part added 11.2 %, head h on
                             # k/v head h mod 4 28 %. NOT seen by the
                             # stream: the router fed RMSNorm_2(h) (0.81 %)
                             # or RMSNorm_1(x) (0.49 for 0.49: the picks
                             # are given), a window of 4,095 or 4,097 keys,
                             # the router's logits in bfloat16: the
                             # gradients, parts (C) and (B) hold those
LOSS_ABS_TOL = 6e-3          # the loss the STEP returned (cross-entropy +
                             # 0.01 balance + 0.001 z), of 8.2-8.3 at step
                             # 63-69: 5.7e-6 to 4.3e-4 sound. Wrong, the
                             # smallest reading over it: RoPE on layer 0
                             # 1.5e-2, SwiGLU 1.6e-2; the absent experts
                             # added 2.4e-2, the unnormalised softmax
                             # 4.5e-2, no RoPE on a window layer 5.0e-2,
                             # head h mod 4 0.79; the router fed a norm
                             # (3.1e-4, 1.2e-3) stays under it: the
                             # gradients tell
# the gradient the step applied, every token of it, of the reference's RMS,
# the worst layer of a family, in five classes: the final norm's scale sees
# the head's backward pass alone; a matrix outside the experts is a sum over
# 16,384 rows; a held expert's matrices see only the ~1,600 rows routed to
# them; a router's gradient is a difference of near equal terms over 64
# logits a token; a norm's scale a sum of cancelling terms over every
# position:
HEAD_GRAD_REL_RMS_TOL = 0.03       # 0.31-0.96 % -> 4.5 % (the unnormalised
                                   # softmax), 5.7 % (RoPE on layer 0), 8.4 %
                                   # (the absent experts), 8.9 % (no RoPE on
                                   # a window layer); SwiGLU's 1.8 % is the
                                   # experts' to tell
MATRIX_GRAD_REL_RMS_TOL = 0.05     # 0.24-1.78 % -> 7.4 % (Wq, the router fed
                                   # RMSNorm_2(h)), 10.8 % (the embedding,
                                   # the unnormalised softmax), 15.4 % (the
                                   # absent experts), 83 % and 133 % (Wq, no
                                   # RoPE where one belongs and one where
                                   # none does), 281 % (head h mod 4)
EXPERT_GRAD_REL_RMS_TOL = 0.1      # 0.6-3.9 % (the gate's) -> 18.4 % (RoPE
                                   # on layer 0), 25 % (no RoPE on a window
                                   # layer), 35-39 % (SwiGLU), 80 % (the
                                   # absent experts), 160 % (the
                                   # unnormalised softmax)
ROUTER_GRAD_REL_RMS_TOL = 0.035    # 0.5-1.75 % -> 4.9 % (the router fed
                                   # RMSNorm_1(x), which the norms' scales
                                   # and the update tell by more; 4.9 % too
                                   # for a SYSTEM whose logits are rounded to
                                   # bfloat16, which part (B) tells), 9.6 %
                                   # (no RoPE on a window layer), 25.5 % (the
                                   # router fed RMSNorm_2(h)), 35 % (SwiGLU)
VECTOR_GRAD_REL_RMS_TOL = 0.1      # 1.4-5.0 % (layer 3's second norm: it
                                   # feeds the held experts alone) -> 22.2 %
                                   # (the router fed RMSNorm_1(x): the first
                                   # norm's scale loses the router's part),
                                   # 31 % (RoPE on layer 0), 95 % (the router
                                   # fed RMSNorm_2(h))
UPDATE_REL_ERR_TOL = 0.05    # the step's change of the weights compared,
                             # |(p' - p) - (AdamW(p, m, v, g_ref) - p)| over
                             # |AdamW(p, m, v, g_ref) - p|, the reference's
                             # float64 AdamW (reference.adamw_after_step,
                             # rounded to the float32 a weight is kept in) on
                             # the state the step started from and the
                             # REFERENCE's gradient, the worst family, at
                             # the cell's lr of 3e-6: 1.0-1.9 % sound -> 9.2 %
                             # (the router fed RMSNorm_1(x)), 15-18 %
                             # (SwiGLU, the unnormalised softmax, RoPE on
                             # layer 0), 37-55 % the others. A state left
                             # unchanged reads 1 in every family
# (B) THE PICKS AND THEIR WEIGHTS, against numpy float64 logits on the
# router's OWN input rows (the layer's input, bfloat16 as the system rounded
# it: `moe_routing_stats` holds the rows behind an optimization barrier) and
# float32 weights, every token of the sample, every layer: an expert the
# system picked and float64 would not must lie within float32 rounding of
# the token's 6th logit, |z64 - theta64| <= NEAR_PICK_REL x the token's
# largest |z64| (2,560 products at "highest" and a top-k); the picks'
# weights against float64 softmax over the system's picks' float64 logits.
# Measured -> bound: 0-1 of 393,216 picks differ a run (2.5e-6), at 7.8e-8
# of the largest logit; the weights 7.5e-7 to 9.1e-7. THE NEAREST PRECISION
# BELOW the one the configuration states, the router's logits rounded to
# bfloat16 (a SYSTEM wrong on purpose, its own run of the cell, seed
# 3000000209): 3,025 picks differ (7.7e-3) at up to 3.6e-3, the weights by
# 4.2e-3, and of the rest only the routers' gradient moves (4.9 %, over its
# limit too; the stream reads 0.29 / 0.48 %). The share's limit lies 200 x
# over the largest sound reading and 15 x under the wrong one
PICKS_DIFFER_MAX_SHARE = 5e-4
NEAR_PICK_REL = 2e-5
PICK_WEIGHT_ABS_TOL = 1e-5
# (C) THE FLOAT32 PARTS AND THE KEPT SETS, against numpy float64 on the
# system's OWN inputs (transformer.attention_terms: the first window layer's
# and the global layer's q and k before and after `_split_heads`, and what
# the layer's own mixer, the function the step's block calls, makes of them):
# what holds whatever the bfloat16 operands did, and what a window off by one
# key, a rotation where none belongs or none where one does each breaks BY
# ITS OWN TERM. The residual stream cannot tell a window off by one.
OWN_OUT_REL_RMS_TOL = 2e-2   # a layer's mixer output (attention over exactly
                             # the keys t - 4096 < s <= t, or every s <= t;
                             # query head j on k/v head j // 7; Wo) on
                             # OUT_ROWS rows at the sequence's start and at
                             # its end, of its RMS, against float64 on the
                             # same bfloat16 q, k, v and Wo: 0.21-0.22 %
                             # the window layer, 0.23-0.24 % the global (the
                             # kernels' bfloat16 probabilities and the
                             # roundings to bfloat16 on the way); head j on
                             # k/v head j mod G reads 94-98 % (CPU, a toy
                             # size)
OWN_WINDOW_EDGE_TOL = 0.1    # of what one key more (s >= t - 4096) or one
                             # fewer would add to the window layer's output
                             # (float64), the share found in the system's
                             # output: 0.001-0.031 sound, 0.984 for a model
                             # that hands its kernels 4,097 keys and 1.009
                             # for 4,095 (each run as a SYSTEM wrong on
                             # purpose: the reference with such a window
                             # reads `correct` TRUE, the stream cannot tell)
OWN_ROPE_REL_RMS_TOL = 1e-2  # the rotated q and k of the first window layer
                             # (all 128 columns, theta 1.5e6), of their RMS,
                             # against float64 on the unrotated bfloat16
                             # columns: 1.65e-3, the result's own rounding
                             # to bfloat16; no rotation reads 0.81 (CPU, a
                             # toy size). The GLOBAL layer's q and k must be
                             # the projection's own columns to the bit
                             # (`own_nope_max_abs_diff` == 0)
OUT_ROWS = 128               # rows [0, 128) (windows cut by the start) and
                             # the sample's last 128 (full windows)
ROPE_STRIDE = 8              # the rotation is held on every eighth position
# (D) THE PAIRS THE WINDOW LAYERS' KERNELS COMPUTE, measured on the chip
# through the first window layer's own mixer (transformer.attention_visits:
# a chunk of keys made NaN at a time, the rows that come out NaN counted)
# against the plan of the kernels' loop bounds (transformer.attention_pairs)
# and against MAX_COMPUTED_OVER_KEPT: whole 512 x 512 tiles of a window of
# 4,096 read 112.496 % (252 tiles a head: 66,060,288 pairs for the 58,722,304
# kept); a kernel that walks every causal tile under the mask 236 %
VISIT_CHUNK = 128            # divides every key tile the kernels choose
MAX_COMPUTED_OVER_KEPT = 1.5
COMPARED_ENTRIES = 1 << 22
EXPERT_ENTRIES = 1 << 19     # of each held expert's matrix (16 a family)
# the leaves whose gradients and updates are compared, by family: every layer
# for the norms, the routers and attention's matrices (the worst), layer 1's
# held experts, both tables
VECTOR_GRADS = ("norm",)
MATRIX_GRADS = ("wq", "wk", "wv", "wo", "embed", "head")
EXPERT_GRADS = ("expert_gate", "expert_up", "expert_down")
GRAD_TOLS = {"lnf_scale": HEAD_GRAD_REL_RMS_TOL,
             "router": ROUTER_GRAD_REL_RMS_TOL,
             **dict.fromkeys(MATRIX_GRADS, MATRIX_GRAD_REL_RMS_TOL),
             **dict.fromkeys(EXPERT_GRADS, EXPERT_GRAD_REL_RMS_TOL),
             **dict.fromkeys(VECTOR_GRADS, VECTOR_GRAD_REL_RMS_TOL)}
EXPERT_LAYER = 1             # the first window layer's experts are compared
INIT_STD = 0.02              # transformer.init_params' draw of the embedding
REHEARSAL_STEPS = 40         # the middle of a run: 15 steps of warm-up and
                             # half of the window's 47-49


def build(config, traffic, seed, devices, batches, spans):
    try:
        from hetu_tpu.models import hf_smallthinker    # noqa: F401
    except ImportError as e:
        # a program from before PR 63 (the parent this cell is tried on
        # first): refused in one line, as a cell whose files are missing
        from benchmark.harness.manifest import ManifestError
        raise ManifestError(
            f"smallthinker-21b-a3b: this program has no loader for it ({e})"
            ": no router on the layer's input, no ReGLU experts, no rotary "
            "form a kind") from e
    return SmallThinkerJob(config, traffic, seed, devices, batches, spans)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def _sampled(name, a):
    """The leaf `name` as compared: every row of a vector or a small matrix,
    of a larger one every n-th row of its first axis, n the least that
    leaves at most COMPARED_ENTRIES entries (EXPERT_ENTRIES of one held
    expert's matrix: the float64 comparison on the host costs ~0.5 s a
    million entries). An entry of a gradient is still a sum over every token
    of the sample."""
    a = np.asarray(a)
    limit = EXPERT_ENTRIES if ".experts." in name else COMPARED_ENTRIES
    return a[::max(1, -(-a.size // limit))]


def _picks_f64(router_in, router, experts, weights):
    """Part (B): every layer's picks `experts` (L, S, k) against the k
    largest float64 logits x W on the router's own input rows `router_in`
    (L, S, D) and weights `router` (L, D, E), and the picks' `weights` (L, S,
    k) against float64 softmax over the SYSTEM's picks' float64 logits ->
    (picks checked, picks that differ, the largest |z64 - theta64| / max
    |z64| over the experts on one side only, the largest |weight - weight64|)."""
    checked = differ = 0
    worst = worst_weight = 0.0
    k = experts.shape[-1]
    for x, w, ours_e, ours_w in zip(router_in, router, experts, weights):
        z = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
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
        mine = np.take_along_axis(z, np.asarray(ours_e), 1)
        mine = np.exp(mine - mine.max(1, keepdims=True))
        worst_weight = max(worst_weight, float(np.abs(
            np.asarray(ours_w, np.float64)
            - mine / mine.sum(1, keepdims=True)).max()))
    return checked, differ, worst, worst_weight


def _rotated_f64(raw, theta, hd):
    """(T / ROPE_STRIDE, heads * hd) unrotated columns at positions 0,
    ROPE_STRIDE, ... -> the float64 rotate-half rotation of all hd columns
    of each head at theta's default frequencies."""
    T = raw.shape[0]
    x = np.asarray(raw, np.float64).reshape(T, -1, hd)
    inv = float(theta) ** (-2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    angle = ROPE_STRIDE * np.arange(T, dtype=np.float64)[:, None, None] * inv
    cos, sin = np.cos(angle), np.sin(angle)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin],
                          -1).reshape(T, -1)


def _row_blocks(T):
    rows = min(OUT_ROWS, T)
    return ((0, rows), (T - rows, T))


def _mixer_out_f64(terms, hd, W, T):
    """A layer's mixer in float64 on the system's own operands, the rows
    `_row_blocks` names: softmax(q k^T / sqrt(hd)) v over the keys s <= t
    (and s > t - W under a window W), query head j on k/v head j // (heads /
    kv heads), through Wo -> (blocks, OUT_ROWS, D)."""
    f64 = lambda a: np.asarray(a, np.float64)
    wo = f64(terms["wo"])
    k_all, v_all = (f64(terms[n]).reshape(T, -1, hd)
                    for n in ("k_own", "v_own"))
    G = k_all.shape[1]
    out = []
    for (lo, hi), q in zip(_row_blocks(T), f64(terms["q_rows"])):
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
        out.append(o.reshape(hi - lo, -1) @ wo)
    return np.stack(out)


def _own_terms_f64(config, window, full, T):
    """Part (C) in numpy float64 from the system's own inputs -> the errors
    of its float32 parts and kept sets: the window layer's rotation of q and
    k; the global layer's q and k against the projection's own columns (NO
    rotation: to the bit); each kind's mixer output over exactly the keys it
    keeps; and the window's edge: how much of what ONE KEY MORE (s >= t - W)
    or one fewer would add to the window layer's output is in the system's,
    the projection of its difference from the float64 output onto the
    difference that key makes (0 for exactly the window's keys, 1 for a
    window off by one)."""
    hd, W = config["head_dim"], config["sliding_window_size"]
    out = {"own_rope_rel_rms_err_window": max(
        _rel_rms(window[n][0], _rotated_f64(
            window[n + "_raw"][0], config["rope_theta"], hd)) for n in "qk"),
        "own_nope_max_abs_diff": max(float(np.abs(
            np.asarray(full[n][0], np.float64)
            - np.asarray(full[n + "_raw"][0], np.float64)).max())
            for n in "qk")}
    for name, terms, w in (("window", window, W), ("full", full, None)):
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


class SmallThinkerJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_smallthinker, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_smallthinker.config_from_hf(config,
                                                        dtype=jnp.bfloat16)
        self.items_per_step = traffic["sequences"] * traffic["seq_len"]

        def init(key):
            params = tfm.init_params(key, cfg)
            # the token embedding at its own scale (config.json `assumed`
            # says why): the initializer's draw, std INIT_STD, scaled
            params["embed"] = params["embed"] * (
                config["assumed"]["embedding_std"] / INIT_STD)
            return params, tfm.init_opt_state(params)

        # weights and optimizer state on the device, in one call
        init = jax.jit(init)
        self._step = tfm.make_train_step(
            cfg, lr=config["assumed"]["learning_rate"])
        # the program's pure routing pass (tokens are arguments, so every
        # seed reads the same entry of the compile cache): which experts the
        # chip holds, the check's part (B), the pick counter of a traced run
        self._routing = jax.jit(lambda p, t: tfm.moe_routing_stats(
            p, t, cfg, terms=True))
        self.batches = batches
        # WHICH experts the chip holds (config.json `assumed.held_experts`),
        # by rows alone, in two looks: the block of adjacent experts of the
        # ring nearest the even share of the picks at the initial weights;
        # then, because the picks drift towards whatever is held, a
        # REHEARSAL of the run to its middle from those weights, ONE expert
        # of the block exchanged for one outside it so that the share of the
        # picks THERE is nearest the even one, and the same seeded weights
        # again with those sixteen held
        self.params, self.opt = init(jax.random.PRNGKey(seed))
        first = self._block_nearest_even(self._picks())
        order = self._hold(first)
        for i in range(REHEARSAL_STEPS):
            batch = jax.device_put(batches[i % len(batches)], self.device)
            _, self.params, self.opt = self._step(
                self.params, self.opt, batch["tokens"], batch["targets"])
        there = np.empty(len(order))
        there[order] = self._picks()           # by expert, not by column
        self.held = self._one_exchanged(there, first)
        self.held_rehearsed = float(there[self.held].sum() / there.sum())
        self.params = self.opt = None
        self.params, self.opt = init(jax.random.PRNGKey(seed))
        order = self._hold(self.held)
        at_start = np.empty(len(order))
        at_start[order] = self._picks()
        self.held_at_start = float(at_start[self.held].sum()
                                   / at_start.sum())
        self._i = 0
        self._loss = None
        self._traced_picks = None
        self._computed = None
        self._moe = None

    def _picks(self):
        """(width,) the picks a COLUMN of the routers takes at the weights as
        they stand, all layers and all of the traffic's batches together."""
        import jax
        return sum(np.asarray(self._routing(self.params, jax.device_put(
            b["tokens"], self.device))["picks"]).sum(0) for b in self.batches)

    def _block_nearest_even(self, picks):
        """`picks` (width,) by expert -> the block of adjacent experts of
        the ring (it may wrap) whose share of the picks is nearest the even
        one, as an array of experts."""
        held, width = self.cfg.n_experts, picks.size
        ring = np.concatenate([picks, picks[:held - 1]])
        start = int(np.argmin(np.abs(
            np.convolve(ring, np.ones(held), "valid") / picks.sum()
            - held / width)))
        return (start + np.arange(held)) % width

    def _one_exchanged(self, picks, block):
        """`block` with the ONE expert exchanged for one outside it that
        brings its share of `picks` (width,) nearest the even one (or with
        none, if none brings it nearer)."""
        outside = np.setdiff1d(np.arange(picks.size), block)
        off = lambda rows: np.abs(rows / picks.sum()
                                  - block.size / picks.size)
        rows = picks[block].sum()
        after = off(rows - picks[block][:, None] + picks[outside][None, :])
        i, j = np.unravel_index(np.argmin(after), after.shape)
        if after[i, j] < off(rows):
            block = block.copy()
            block[i] = outside[j]
        return block

    def _hold(self, experts):
        """The routers' columns put in an order in which `experts` are the
        ones held (columns [first_expert_held, + held), first_expert_held
        stays): every column is the same seeded draw, so the weights are as
        random as they were. On the host, as keye-vl-2.0-30b-a3b's adapter
        rolls its own (the TPU's compiler aborts on some rolls). -> the
        expert each column now is."""
        import jax
        from hetu_tpu.models import transformer as tfm
        cfg = self.cfg
        first = cfg.router.first_held
        others = np.setdiff1d(np.arange(cfg.router.width), experts)
        order = np.concatenate([others[:first], experts, others[first:]])
        self.params["blocks"] = tfm.blocks_of_runs([
            {**run, "router": jax.device_put(
                np.asarray(run["router"])[..., order], self.device)}
            for run in tfm.run_blocks(cfg, self.params["blocks"])])
        return order

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
        """The pick counter of a traced run: the model has no selection bias
        beside which the step could write its counts, so the picks of the
        traced steps are what the program's own routing pass
        (`moe_routing_stats`, the function the check's part (B) holds to
        float64) takes on THOSE steps' batches at the weights the traced
        window starts from. Made once, when the warm-up's last step has
        drained and before the profiler opens: nothing of it is in the trace.
        The weights move by at most the cell's lr (3e-6) a step in the five
        traced steps, so the step's own picks differ from these by the
        tokens whose 6th and 7th logits lie that close (a frequent token's
        all at once: config.json `assumed.initial_routing`), so these counts
        are the routing PASS's, not the traced steps' own (PERF.md section
        7)."""
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
        from benchmark.reduce import smallthinker
        out = {"flops_per_item": smallthinker.train_flops_per_token(
            self.config, self.traffic["seq_len"]),
            "attn_pairs": self._attn_pairs()}
        if self._moe is not None:
            out["moe"] = self._moe
        if self._traced_picks:
            out["traced_picks"] = self._traced_picks
        return out

    def _hf_names(self):
        """{a name of GRAD_TOLS: the groups of HF names whose gradients it
        covers}: a group is one leaf of one layer (the held experts'
        matrices of a layer are one); the worst group is reported."""
        from hetu_tpu.models import hf_smallthinker as hs
        cfg = self.cfg
        layers = range(cfg.n_layers)
        first = cfg.router.first_held
        experts = lambda w: [hs.expert_name(EXPERT_LAYER, first + e, w)
                             for e in range(cfg.n_experts)]
        names = {"w" + x: [[hs.hf_name(i, p)] for i in layers]
                 for x, p in zip("qkvo", hs.QKV + (hs.WO,))}
        names.update(
            norm=[[hs.hf_name(i, p)] for i in layers
                  for p in hs.NORMS.values()],
            lnf_scale=[[hs.FINAL_NORM]], embed=[[hs.EMBED]], head=[[hs.HEAD]],
            router=[[hs.hf_name(i, hs.ROUTER)] for i in layers],
            expert_gate=[experts("w1")], expert_up=[experts("w3")],
            expert_down=[experts("w2")])
        return {n: names[n] for n in GRAD_TOLS}

    def check(self, reference):
        """One more call of the timed step on the correctness sample
        (`_observe`), and what it returned against the float32 reference
        GIVEN the system's own picks (`_compare`): its loss, the gradient it
        applied, the weights it left (the reference's AdamW); the residual
        stream after each run of layers. Part (B): the picks and their
        weights against float64 logits on the routers' own input rows. Part
        (C): the rotation, the absence of one, each kind's kept keys and the
        window's edge against float64 on the system's own inputs. Part (D):
        the pairs the window kernels compute."""
        return self._compare(reference, self._observe())

    def _observe(self):
        """The system's side of the check, nothing of the reference: what
        the timed step returned on the sample, and what the program's own
        pure functions (`_through_run`, `moe_routing_stats`,
        `attention_terms`, `attention_visits`) make of the weights it
        started from."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_smallthinker, transformer as tfm
        from benchmark.generators import lm_zipf

        cfg, config = self.cfg, self.config
        sample = jax.device_put(lm_zipf.generate(
            self.traffic, config, self.seed,
            sequences=self.traffic["check_sequences"])[0], self.device)
        tokens, targets = sample["tokens"], sample["targets"]
        T = tokens.shape[1]
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
                sd = hf_smallthinker.state_dict_from_params(tree, cfg)
                return {n: _sampled(n, sd[n]) for n in wanted}

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

        # one program a question, run one after another: together their
        # working sets would stand beside each other
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
                    "q_rows": rows(terms["q"]),
                    "out_rows": rows(terms["out"]),
                    "k_own": k, "v_own": own_kv(terms["v"]),
                    "wo": terms["wo"].astype(cfg.dtype)}

        own = jax.jit(own, static_argnums=2)
        chunk = min(VISIT_CHUNK, T)
        visits = jax.jit(lambda p, t: tfm.attention_visits(
            p, t[:1], cfg, "window", chunk))
        stream = jax.device_get(jax.jit(hidden)(params, tokens))
        stats = jax.device_get(self._routing(params, tokens))
        router_w = np.concatenate(
            [np.asarray(b["router"])
             for b in tfm.run_blocks(cfg, params["blocks"])])
        picks = _picks_f64(stats.pop("router_in"), router_w,
                           stats["experts"], stats.pop("weights"))
        del router_w
        own_terms = _own_terms_f64(
            config, jax.device_get(own(params, tokens, "window")),
            jax.device_get(own(params, tokens, "attention")), T)
        self._computed = int(np.asarray(visits(params, tokens)).sum()) * chunk
        sd = hf_smallthinker.state_dict_from_params(params, cfg)
        del params             # the reference holds its own (HF) views now
        return {"tokens": tokens, "targets": targets, "wanted": wanted,
                "before": before, "after": after, "step_no": step_no,
                "step_loss": step_loss, "stream": stream, "stats": stats,
                "picks": picks, "own_terms": own_terms, "sd": sd,
                "seconds": {"step": t1 - t0,
                            "system": time.perf_counter() - t1}}

    def _compare(self, reference, seen):
        """`_observe`'s findings against reference.py, eagerly (the
        reference jits its layers and head itself) -> the check's result."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import transformer as tfm

        cfg, config = self.cfg, self.config
        r = cfg.router
        tokens, targets, sd = seen["tokens"], seen["targets"], seen["sd"]
        before, after, stats = seen["before"], seen["after"], seen["stats"]
        hf_names = self._hf_names()
        T = tokens.shape[1]
        t2 = time.perf_counter()
        loads, held_pct = _loads(stats["picks"], r.first_held, cfg.n_experts)
        self._moe = {"picks": stats["picks"].tolist(),
                     "max_over_mean": loads,
                     "held": stats["held"].tolist(),
                     "dropped": int(stats["dropped"].sum()),
                     "entropy": stats["entropy"].tolist()}

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
        want_grads = {n: _sampled(n, g)
                      for n, g in jax.device_get(want_grads).items()}
        del want_hidden
        t3 = time.perf_counter()
        # the gradient the step applied, from AdamW's first moment; and the
        # weights it left against the reference's AdamW on its own gradient
        adamw = config["assumed"]["adamw"]
        b1 = adamw["b1"]
        f64 = lambda a: np.asarray(a, np.float64).reshape(-1)
        pooled = lambda tree, group: np.concatenate(
            [f64(tree[h]) for h in group])
        grad_err, update_err, grad_worst = {}, {}, {}
        for n, groups in hf_names.items():
            grad_err[n] = update_err[n] = 0.0
            for group in groups:
                p, m, v, g = (pooled(tree, group) for tree in (
                    before["p"], before["m"], before["v"], want_grads))
                got_g = (pooled(after["m"], group) - b1 * m) / (1.0 - b1)
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

        # the pairs the kernels computed, measured, against the plan of their
        # loops' bounds; the plan's kept pairs against the closed form: a
        # window layer keeps sum_t min(t + 1, W) pairs of T (T + 1) / 2
        W = config["sliding_window_size"]
        pairs = self._attn_pairs()
        causal = T * (T + 1) // 2
        closed = {"window": sum(min(t + 1, W) for t in range(T)),
                  "attention": causal}
        picks_checked, picks_differ, picks_worst, weight_err = seen["picks"]
        step_loss = seen["step_loss"]
        out = {"loss": step_loss, "reference_loss": want_loss,
               "loss_abs_err": abs(step_loss - want_loss),
               "hidden_rel_rms_err": hidden_err,
               "picks_checked": picks_checked,
               "picks_that_differ": picks_differ,
               "picks_differ_share": picks_differ / max(picks_checked, 1),
               "picks_differ_worst_distance": picks_worst,
               "pick_weight_abs_err": weight_err,
               "held_picks": self._moe["held"],
               "held_pick_pct": held_pct,
               "held_experts": self.held.tolist(),
               "held_pick_pct_at_start": 100.0 * self.held_at_start,
               "held_pick_pct_rehearsed": 100.0 * self.held_rehearsed,
               "dropped_picks": self._moe["dropped"],
               "load_max_over_mean": loads,
               **seen["own_terms"],
               "kept_pair_pct": {k: v["kept_pct"] for k, v in pairs.items()},
               "computed_pair_pct": {k: v["computed_pct"]
                                     for k, v in pairs.items()},
               "planned_window_pairs": pairs["window"]["planned"],
               "step": seen["step_no"],
               "grad_rel_rms_err": grad_err,
               "grad_worst_leaf": grad_worst,
               "update_rel_err": update_err,
               "sample": list(tokens.shape),
               "seconds": {**seen["seconds"], "reference": t3 - t2,
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
            "pick_weights": out["pick_weight_abs_err"] <= PICK_WEIGHT_ABS_TOL,
            "own_out": max(out["own_out_rel_rms_err_window"],
                           out["own_out_rel_rms_err_full"]
                           ) <= OWN_OUT_REL_RMS_TOL,
            "own_window_edge":
                out["own_window_edge_share"] <= OWN_WINDOW_EDGE_TOL,
            "own_rope":
                out["own_rope_rel_rms_err_window"] <= OWN_ROPE_REL_RMS_TOL,
            "own_nope": out["own_nope_max_abs_diff"] == 0.0,
            "pairs": (set(pairs) == set(closed) and all(
                pairs[k]["kept"] == closed[k]
                and pairs[k]["causal"] == causal for k in pairs)
                and pairs["window"]["computed"] == pairs["window"]["planned"]
                # off the chip the step takes the dot path: dense under a mask
                and (pairs["window"]["computed"] <= MAX_COMPUTED_OVER_KEPT
                     * closed["window"] or jax.default_backend() != "tpu")),
            "grads": (set(grad_err) == set(GRAD_TOLS) and all(
                err <= GRAD_TOLS[n] for n, err in grad_err.items())),
            "update": max(update_err.values()) <= UPDATE_REL_ERR_TOL}
        out["failed_parts"] = [n for n, ok in parts.items() if not ok]
        out["ok"] = not out["failed_parts"]
        return out

    def close(self):
        pass
