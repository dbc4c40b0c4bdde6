"""kanana-2-30b-a3b as a user's job script builds it: the published
config.json (cut to one chip's share, config.json `reduced`) through
`hf_deepseek_v3.config_from_hf`, weights from the program's own initialiser,
`transformer.make_train_step` (next-token loss on the untied head, AdamW in
the step, the routers' selection bias by its sign rule after it). Only
architecture, shapes, optimizer, the bias's rate and compute dtype are
stated; attention implementation, fused cross-entropy, recomputation, the
grouped matmul and kernel mode stay the program's defaults.
"""
import dataclasses
import time

import numpy as np

# Agreement with the float32 reference (reference.py, given the same share)
# on the correctness sample (one sequence of 8,192 tokens, another stream of
# the same seed) with the weights the window left. The system computes in
# bfloat16 (8 bits of mantissa) with float32 accumulation; the router's
# scores, selection and normalisation, the latent's RMSNorm, the attention
# kernels' softmax statistic, the norms' statistics and the loss are float32;
# the reference is float32 at "highest" precision throughout. Measured on the
# v5e over 15 seeds after 29-34 steps (my chip runs, PR 39; PERF.md section 6
# has the seeds), measured -> bound, each bound 2-4x above the largest seen
# (a share: below the smallest). The routers are near their initialisation
# (lr 3e-6) and every token ranks the 128 experts much alike (config.json
# `assumed`), so 3-4 % of the (token, pick) pairs fall on another expert than
# the reference's: that, not the matmuls' bfloat16, is most of every error
# below the first layer (a flipped pick changes a token's whole path).
HIDDEN_REL_RMS_TOL = 8e-2    # the residual stream after layer 0 (latent
                             # attention + dense MLP: 0.68-0.76 %) and after
                             # the whole stack (2.01-2.75 %), of its RMS
LOSS_ABS_TOL = 2e-3          # the loss on 8,192 tokens, of 6.9-7.1: 2.4e-6
                             # to 6.8e-4
GRAD_LOSS_ABS_TOL = 8e-3     # on the gradient sample's GRAD_TOKENS: 7.2e-5
                             # to 1.9e-3
SAME_EXPERT_MIN_SHARE = 0.93    # (token, pick) pairs on an expert the
                                # reference picks too, the worst layer:
                                # 95.9-97.0 % (lfm2's 32 scores a token lie
                                # further apart: 98.5)
PICKS_MOVED_MAX_SHARE = 0.01    # picks that `jax.grad`'s own forward pass
                                # counted on another expert than the pure
                                # function beside the step did (two programs,
                                # the same near-ties): 0.28-0.32 %
# gradients on the sample's first GRAD_TOKENS tokens (the reference's
# attention backward keeps a block of scores a query block), of the
# reference's RMS, the worst layer of a kind, in five classes: the final
# norm's scale sees the head's backward pass alone (0.8-1.7 %); a matrix
# outside the expert block is a sum over GRAD_TOKENS rows (1.5-4.0 %: Wq
# 2.6-4.0, Wkv_a 1.5-2.5, the shared expert's 1.5-2.6); a held expert's
# matrices see only the rows routed to them, flipped ones among them
# (10.7-18.6 %); a router's gradient IS the picks' (16.7-45.4 %: one seed of
# 15 above 35); a vector's a sum of cancelling terms over every position
# (1.5-4.1 %: ln2_scale 2.3-4.1, the latent's norm 1.5-2.8)
GRAD_TOKENS = 2048
HEAD_GRAD_REL_RMS_TOL = 0.05
MATRIX_GRAD_REL_RMS_TOL = 0.12
EXPERT_GRAD_REL_RMS_TOL = 0.5
ROUTER_GRAD_REL_RMS_TOL = 0.9       # under 1: a gradient of zeros fails
VECTOR_GRAD_REL_RMS_TOL = 0.15
# The float32 parts against numpy float64 on the system's OWN inputs
# (transformer.router_terms: the first expert layer's router;
# transformer.mla_terms: the first layer's latent norm, and the q, k, v its
# kernels take, whose forward kernel is run on them for its statistic): what
# holds float32 to float32 whatever the bfloat16 operands did, and what ONE
# bfloat16 pass where float32 is stated breaks: each alone reads `correct:
# false`, by its own term and no other (the job's own check with one
# function patched to round through bfloat16; my chip runs, PR 39, seed
# 2147484812). Against the reference those passes are invisible (the stack's
# residual stream 2.29-2.53 % for 2.17-2.50, the same expert on 96.0-96.8 %
# for 96.5-97.0): only these can tell. Each bound lies between its two
# readings.
OWN_SCORE_ABS_TOL = 2e-5         # max |s - sigmoid(x W_g)| of scores in (0, 1):
                                 # 1.26-1.32e-6 (the TPU's float32 exp);
                                 # logits and scores in bfloat16: 2.71e-3
OWN_WEIGHT_REL_TOL = 2e-5        # max |w - 2.448 s_i / (sum + 1e-20)| / w over
                                 # picks: 2.5-3.0e-7; normalised in bfloat16:
                                 # 1.02e-2
OWN_LATENT_REL_RMS_TOL = 1e-5    # RMSNorm(c) of the latent, of its RMS:
                                 # 5.3-6.7e-8; statistic and scaling in
                                 # bfloat16: 2.54e-3
OWN_LSE_ABS_TOL = 1e-3           # the forward kernel's row statistic log sum
                                 # exp(q k^T / sqrt(192)) over 7,681-8,192
                                 # keys, of 9-10, against float64 on the same
                                 # bfloat16 q and k, LSE_ROWS rows of two
                                 # heads: 7.7e-5 to 1.1e-4 (the TPU's float32
                                 # exp and log, the running sum rescaled 16
                                 # times); running max, exp, sum and log in
                                 # bfloat16: 5.24e-2
LSE_ROWS = 512                   # the sample's last rows (the most keys) of
LSE_HEADS = (0, 31)              # an even head (its 192 lanes tile-aligned)
                                 # and an odd one (they start mid-tile)
# the leaves whose gradients are compared, by the trunk's names: every layer
# for the vectors and the routers (the worst is reported), one matrix a kind.
# NOT the embedding (8,192 sparse rows) nor the 33M-entry head
VECTOR_GRADS = ("ln1_scale", "ln2_scale", "kv_norm")
MATRIX_GRADS = ("wq", "wkv_a", "wkv_b", "wo", "dense_w1_layer0",
                "shared_w1", "shared_w2")
EXPERT_GRADS = ("expert_w1_layer1", "expert_w2_layer1")
GRAD_TOLS = {"lnf_scale": HEAD_GRAD_REL_RMS_TOL,
             "router": ROUTER_GRAD_REL_RMS_TOL,
             **dict.fromkeys(MATRIX_GRADS, MATRIX_GRAD_REL_RMS_TOL),
             **dict.fromkeys(EXPERT_GRADS, EXPERT_GRAD_REL_RMS_TOL),
             **dict.fromkeys(VECTOR_GRADS, VECTOR_GRAD_REL_RMS_TOL)}


def build(config, traffic, seed, devices, batches, spans):
    return KananaJob(config, traffic, seed, devices, batches, spans)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def _own_terms_f64(router, mla, r, eps):
    """In numpy float64 from the system's own inputs -> the four errors of
    its float32 parts: the scores against sigmoid(x W_g); the picks' weights
    against ITS scores of ITS picks over (their sum + the router's epsilon),
    times the scale; the latent's norm against RMSNorm of ITS bfloat16
    latent; the forward kernel's row statistic against log sum exp of ITS
    bfloat16 q and k's scores over the keys a row sees."""
    f64 = lambda x: np.asarray(x).astype(np.float64)
    scores = 1.0 / (1.0 + np.exp(-(f64(router["x"]) @ f64(router["router"]))))
    got = f64(router["scores"])
    top = np.take_along_axis(got, np.asarray(router["experts"]), -1)
    if r.normalize:
        top = top / (top.sum(-1, keepdims=True) + r.normalize_eps)
    top = top * r.scale
    c = f64(mla["c"])
    latent = c / np.sqrt((c * c).mean(-1, keepdims=True) + eps) * f64(
        mla["kv_norm"])
    lse_err = 0.0
    for q, k, lse in zip(f64(mla["q_rows"]), f64(mla["k_heads"]),
                         f64(mla["lse_rows"])):
        s = q @ k.T / np.sqrt(q.shape[-1])              # (rows, T)
        first = k.shape[0] - q.shape[0]
        seen = np.arange(k.shape[0])[None, :] <= (
            first + np.arange(q.shape[0]))[:, None]
        s = np.where(seen, s, -np.inf)
        m = s.max(-1, keepdims=True)
        want = (m + np.log(np.exp(s - m).sum(-1, keepdims=True)))[:, 0]
        lse_err = max(lse_err, float(np.max(np.abs(lse - want))))
    return {"own_score_abs_err": float(np.max(np.abs(got - scores))),
            "own_weight_rel_err": float(np.max(
                np.abs(f64(router["weights"]) - top) / top)),
            "own_latent_rel_rms_err": _rel_rms(mla["latent"], latent),
            "own_lse_abs_err": lse_err}


def _loads(picks, first, n_held):
    """(layers, E) picks an expert -> ([the fullest expert's load over the
    mean, a layer], the share of all picks on the experts held, in %)."""
    picks = np.asarray(picks, np.float64)
    return ((picks.max(-1) / picks.mean(-1)).tolist(),
            100.0 * picks[:, first:first + n_held].sum() / picks.sum())


class KananaJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_deepseek_v3, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_deepseek_v3.config_from_hf(
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
        # (one 2 KB device op, no host read); else read at a sync
        self._picks_of = jax.jit(lambda m: jnp.concatenate(
            [b[tfm.ROUTER_BIAS] for b in tfm.run_blocks(cfg, m["blocks"])
             if tfm.ROUTER_BIAS in b]))
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
        from benchmark.reduce import mla
        out = {"flops_per_item": mla.kanana_train_flops_per_token(
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
        from hetu_tpu.models import hf_deepseek_v3 as hd, transformer as tfm
        cfg = self.cfg
        kinds = tfm.layer_kinds(cfg)
        every = range(len(kinds))
        moe = [i for i, k in enumerate(kinds) if tfm.experts_of(cfg, k)]
        dense = [i for i, k in enumerate(kinds)
                 if not tfm.experts_of(cfg, k)]
        first = cfg.router.first_held
        experts = lambda i, w: [hd.expert_name(i, first + e, w)
                                for e in range(cfg.n_experts)]
        names = {n: [[hd.hf_name(i, part)] for i in every]
                 for n, part in {**hd.NORMS, **hd.ATTN_LINEARS,
                                 "kv_norm": hd.KV_NORM,
                                 "wkv_b": hd.KV_B}.items()}
        names.update(
            lnf_scale=[["model.norm.weight"]],
            router=[[hd.hf_name(i, hd.ROUTER)] for i in moe],
            dense_w1_layer0=[[hd.hf_name(dense[0], "mlp." + hd.MLP["w1"])]],
            shared_w1=[[hd.shared_name(i, "w1")] for i in moe],
            shared_w2=[[hd.shared_name(i, "w2")] for i in moe],
            expert_w1_layer1=[experts(moe[0], "w1")],
            expert_w2_layer1=[experts(moe[0], "w2")])
        return names

    def check(self, reference):
        """The system's loss, residual stream and routing on a seeded
        sample, its gradients on the sample's first `GRAD_TOKENS` tokens, the
        bias's move and its float32 parts, against the float32 reference
        (handed the same weights under their HF names, and the same share)
        and numpy float64."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.kernels import flash_attention as fa
        from hetu_tpu.models import hf_deepseek_v3, transformer as tfm
        from benchmark.generators import lm_zipf

        cfg, config = self.cfg, self.config
        r, rate = cfg.router, cfg.router.bias_rate
        routed = r.width or cfg.n_experts
        self.opt = None        # the job is over: its 4.6 GB are the check's
        sample = jax.device_put(lm_zipf.generate(
            self.traffic, config, self.seed,
            sequences=self.traffic["check_sequences"])[0], self.device)
        tokens, targets = sample["tokens"], sample["targets"]
        T = tokens.shape[1]
        few = min(GRAD_TOKENS, T)
        rows = min(LSE_ROWS, T)
        lse_heads = [h for h in LSE_HEADS if h < cfg.n_heads]
        kind0, n0 = tfm.layer_runs(cfg)[0]
        first_run = dataclasses.replace(
            cfg, n_layers=n0, layer_types=cfg.layer_types[:n0],
            n_dense_layers=min(cfg.n_dense_layers, n0))
        hf_names = self._hf_names()
        wanted = sorted(h for groups in hf_names.values()
                        for group in groups for h in group)
        t0 = time.perf_counter()

        def own_mla(params, tokens):
            """The first layer's float32 parts with their inputs: the
            latent and its norm; the rows of q, the keys and the forward
            kernel's statistic of `lse_heads`, the sample's last `rows`."""
            m = tfm.mla_terms(params, tokens, cfg)
            B = tokens.shape[0]
            # the timed path's own kernel on the arrays it takes (off the
            # chip, where the step takes the dot path: interpreted)
            _, lse = fa._fwd_pallas(
                (m["q"], m["k"], m["v"]), cfg.n_heads, None,
                1.0 / np.sqrt(cfg.mla.qk_dim), True, None, None,
                interpret=jax.default_backend() != "tpu")
            lse = lse.reshape(B, cfg.n_heads, T)
            cols = lambda x, h: x[0, :, h * cfg.mla.qk_dim:
                                  (h + 1) * cfg.mla.qk_dim]
            return {"c": m["c"], "kv_norm": m["kv_norm"],
                    "latent": m["latent"],
                    "q_rows": jnp.stack([cols(m["q"], h)[T - rows:]
                                         for h in lse_heads]),
                    "k_heads": jnp.stack([cols(m["k"], h)
                                          for h in lse_heads]),
                    "lse_rows": jnp.stack([lse[0, h, T - rows:]
                                           for h in lse_heads])}

        # tokens and targets are arguments, not constants of the program:
        # every seed then reads the same entry of the compile cache
        def forward(params, tokens, targets):
            after_stack, _ = tfm.forward_hidden(params, tokens, cfg)
            after_run, _ = tfm.forward_hidden(
                {**params, "blocks": tfm.run_blocks(cfg, params["blocks"])[0]},
                tokens, first_run)
            # the bias's "gradient" is the picks the forward pass counted:
            # the rule moves the bias by them, as the step does
            g = jax.grad(tfm.loss_fn)(params, tokens, targets, cfg)
            moved, _ = tfm.move_router_bias(params, g, {"m": g}, rate)
            bias_of = lambda p: jnp.concatenate(
                [b[tfm.ROUTER_BIAS] for b in tfm.run_blocks(cfg, p["blocks"])
                 if tfm.ROUTER_BIAS in b])
            return (tfm.loss_fn(params, tokens, targets, cfg),
                    after_run.astype(jnp.float32),
                    after_stack.astype(jnp.float32),
                    tfm.moe_routing_stats(params, tokens, cfg),
                    tfm.router_terms(params, tokens, cfg),
                    own_mla(params, tokens),
                    bias_of(params), bias_of(moved), bias_of(g))

        def grads(params, tokens, targets):
            loss, g = jax.value_and_grad(tfm.loss_fn)(params, tokens,
                                                      targets, cfg)
            sd = hf_deepseek_v3.state_dict_from_params(g, cfg)
            return loss, {n: sd[n] for n in wanted}

        (loss, after_run, after_stack, stats, router, mla, bias, bias_moved,
         step_counts) = jax.jit(forward)(self.params, tokens, targets)
        own = _own_terms_f64(jax.device_get(router), jax.device_get(mla), r,
                             cfg.ln_eps)
        del router, mla
        stats, bias, bias_moved, step_counts = jax.device_get(
            (stats, bias, bias_moved, step_counts))
        # to the host: the reference's backward pass needs the device
        few_loss, got_grads = jax.device_get(jax.jit(grads)(
            self.params, tokens[:, :few], targets[:, :few]))
        t1 = time.perf_counter()
        loads, held_pct = _loads(stats["picks"], r.first_held, cfg.n_experts)
        self._moe = {"picks": stats["picks"].tolist(),
                     "max_over_mean": loads,
                     "held": stats["held"].tolist(),
                     "dropped": int(stats["dropped"].sum()),
                     "entropy": stats["entropy"].tolist()}

        sd = hf_deepseek_v3.state_dict_from_params(self.params, cfg)
        self.params = None     # the reference holds its own (HF) views now
        # eagerly: the reference jits its layers and its head itself
        want_loss, want = reference.loss_terms(sd, tokens, targets, config)
        hidden_err = {
            f"after_{kind0}_run": _rel_rms(after_run, want["hidden"][n0 - 1]),
            "after_stack": _rel_rms(after_stack, want["hidden"][-1])}
        want_loss = float(want_loss)
        want_experts, want_counts = jax.device_get(
            (want["experts"], want["counts"]))
        del want, after_run, after_stack
        # a pair is the same where the reference picks that expert for that
        # token too, whatever its rank; a layer
        same = np.mean(np.any(
            stats["experts"][..., :, None] == want_experts[..., None, :], -1),
            (1, 2))
        flipped = np.ceil((1.0 - same) * want_experts[0].size)
        # the bias after a step on this sample: the system's rule on its own
        # counts against the reference's rule on the reference's counts. An
        # entry may differ only where the flipped picks can explain it: the
        # reference's count within `flipped` picks of the mean
        want_bias = reference.bias_after_step(bias, want_counts, rate)
        differs = np.abs(bias_moved - want_bias) > rate / 2
        near = np.abs(want_counts - want_counts.mean(-1, keepdims=True)
                      ) <= flipped[:, None]
        t2 = time.perf_counter()
        want_few_loss, want_grads = jax.device_get(reference.grads_of(wanted)(
            sd, tokens[:, :few], targets[:, :few], config))
        pooled = lambda g, group: np.concatenate(
            [np.asarray(g[h]).reshape(-1) for h in group])
        grad_err = {n: max(_rel_rms(pooled(got_grads, group),
                                    pooled(want_grads, group))
                           for group in groups)
                    for n, groups in hf_names.items()}
        t3 = time.perf_counter()

        out = {"loss": float(loss), "reference_loss": want_loss,
               "loss_abs_err": abs(float(loss) - want_loss),
               "hidden_rel_rms_err": hidden_err,
               "same_expert_share": same.tolist(),
               "held_picks": self._moe["held"],
               "held_pick_pct": held_pct,
               "reference_held_picks": want_counts[
                   :, r.first_held:r.first_held + cfg.n_experts
               ].sum(-1).tolist(),
               "dropped_picks": self._moe["dropped"],
               "load_max_over_mean": loads,
               # the picks `jax.grad` carried out of ITS forward pass
               # against the pure function's: two programs, so a near-tie
               # may fall the other way; of all picks
               "bias_gradient_picks_moved_share": float(
                   np.abs(step_counts - stats["picks"]).sum() / 2
                   / stats["picks"].sum()),
               "bias_entries_that_differ": int(differs.sum()),
               "bias_entries_unexplained": int((differs & ~near).sum()),
               "grad_tokens": few,
               "grad_loss_abs_err": abs(float(few_loss) - float(want_few_loss)),
               "grad_rel_rms_err": grad_err, **own,
               "by_sync": [
                   dict(zip(("steps", "load_max_over_mean", "held_pick_pct"),
                            (i,) + _loads(p, r.first_held, cfg.n_experts)))
                   for i, p in self._sync_picks],
               "sample": list(tokens.shape), "routed_experts": routed,
               "seconds": {"system": t1 - t0, "reference_forward": t2 - t1,
                           "reference_gradients": t3 - t2}}
        out["ok"] = bool(
            np.isfinite(out["loss"])
            and out["loss_abs_err"] <= LOSS_ABS_TOL
            and out["grad_loss_abs_err"] <= GRAD_LOSS_ABS_TOL
            and max(hidden_err.values()) <= HIDDEN_REL_RMS_TOL
            and min(out["same_expert_share"]) >= SAME_EXPERT_MIN_SHARE
            and out["dropped_picks"] == 0
            and out["bias_gradient_picks_moved_share"]
            <= PICKS_MOVED_MAX_SHARE
            and out["bias_entries_unexplained"] == 0
            and set(grad_err) == set(GRAD_TOLS)
            and all(err <= GRAD_TOLS[n] for n, err in grad_err.items())
            and out["own_score_abs_err"] <= OWN_SCORE_ABS_TOL
            and out["own_weight_rel_err"] <= OWN_WEIGHT_REL_TOL
            and out["own_latent_rel_rms_err"] <= OWN_LATENT_REL_RMS_TOL
            and out["own_lse_abs_err"] <= OWN_LSE_ABS_TOL)
        return out

    def close(self):
        pass
