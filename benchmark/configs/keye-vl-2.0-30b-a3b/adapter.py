"""keye-vl-2.0-30b-a3b (the language model) as a user's job script builds it:
the published config.json (cut to one chip's share, config.json `reduced`)
through `hf_keye.config_from_hf`, weights from the program's own initialiser,
`transformer.make_train_step` (next-token loss on the untied head, the
routers' two losses, the indexers' KL loss, AdamW in the step on every leaf).
Only architecture, shapes, optimizer and compute dtype are stated; attention
implementation, fused cross-entropy, recomputation, the grouped matmul and
kernel mode stay the program's defaults.
"""
import dataclasses
import time

import numpy as np

# The check has TWO parts, because a key whose index score is within rounding
# of its row's 2,048th flips between a bfloat16-operand system and a float32
# reference, and a flipped key moves the attention's output by a step no
# tolerance on values can cover. The routers' picks are the same kind of
# thing (an expert whose logit is within rounding of a token's 8th), so (B)
# holds them the same way.
#
# (A) VALUES, given the system's OWN kept sets and its OWN expert picks: the
# float32 reference (reference.py at "highest", handed the same weights under
# their HF names, the same share, the system's kept sets as boolean masks and
# its picks) against the system on the correctness sample (one sequence of
# 16,384 tokens, another stream of the same seed, the weights the window
# left). The system computes in bfloat16 (8 bits of mantissa) with float32
# accumulation; the index scores, the selection, the softmax statistic, the
# head-summed probabilities, the KL, the router and the norms' statistics are
# float32. Measured -> bound on the v5e (my chip runs, PR 44; PERF.md section
# 6 has the seeds).
HIDDEN_REL_RMS_TOL = 3e-2    # the residual stream after layer 0 and after the
                             # stack, of its RMS: bfloat16 matmuls alone
                             # (kanana-2-30b-a3b's first layer reads 0.7 %;
                             # with the picks given no flipped pick adds)
LOSS_ABS_TOL = 5e-3          # the whole loss on 16,384 tokens, of ~10
INDEX_LOSS_REL_TOL = 2e-3    # L_I a layer, of the reference's: the target's
                             # probabilities are rebuilt from bfloat16 q and
                             # k: 8.5e-4 the worst layer of 20 runs; the KL
                             # of a block in bfloat16 reads 2.8e-3-6.1e-3 a
                             # layer (my chip runs, PR 44)
ROUTER_LOSS_REL_TOL = 2e-2   # the balance and the z loss, summed over layers
# gradients on the sample's first GRAD_TOKENS tokens (more than top-k, so the
# selection bites there too; the reference's backward keeps a block of
# scores a query block), given the system's kept sets and picks of THAT
# prefix, of the reference's RMS, the worst layer of a kind
GRAD_TOKENS = 4096
GRAD_LOSS_ABS_TOL = 8e-3
HEAD_GRAD_REL_RMS_TOL = 0.05
MATRIX_GRAD_REL_RMS_TOL = 0.12
INDEXER_GRAD_REL_RMS_TOL = 0.2     # from L_I alone: the difference of two
                                   # distributions a pair
EXPERT_GRAD_REL_RMS_TOL = 0.2
ROUTER_GRAD_REL_RMS_TOL = 0.5
VECTOR_GRAD_REL_RMS_TOL = 0.15
# (B) THE KEPT SETS, against numpy float64 index scores on the system's OWN
# index queries, keys and weights (bfloat16 q and k as the system rounded
# them, float32 w): CHECK_ROWS query rows a layer, spread over the sequence.
# A key the system kept and float64 would not (or the reverse) must lie
# within float32 rounding of the row's threshold: |I64 - theta64| <=
# NEAR_THRESHOLD_REL x the row's largest |I64|. 64 products a head and 16
# heads accumulate in float32: 2^-24 x sqrt(1,024) x a few; index scores in
# bfloat16 (2^-8) would put flipped keys two orders further out.
CHECK_ROWS = 512
KEPT_DIFFER_MAX_SHARE = 2e-5     # of the kept keys of the checked rows: 0-2
                                 # of 3,928,688 a run (5.1e-7); index scores
                                 # rounded to bfloat16 8.9e-4
NEAR_THRESHOLD_REL = 2e-5        # 4.3e-8 the worst of 20 runs; in bfloat16
                                 # 2.9e-3 (my chip runs, PR 44)
# and THE PICKS, against numpy float64 logits on the router's OWN input rows
# (bfloat16 as the system rounded them) and float32 weights, every token of
# the sample, every layer: an expert the system picked and float64 would not
# must lie within float32 rounding of the token's 8th logit, |l64 - theta64|
# <= NEAR_PICK_REL x the token's largest |l64| (2,048 products at "highest",
# a float32 softmax and top-k). Measured -> bound (my chip runs, PR 44): 0 of
# 524,288 picks differ a run; logits and scores rounded to bfloat16: 2,616
# (5.0e-3) at up to 4.0e-3.
PICKS_DIFFER_MAX_SHARE = 2e-5
NEAR_PICK_REL = 2e-5
# the leaves whose gradients are compared, by the trunk's names
VECTOR_GRADS = ("ln1_scale", "ln2_scale", "q_norm", "k_norm")
MATRIX_GRADS = ("wq", "wk", "wv", "wo")
INDEXER_GRADS = ("wq_idx", "wk_idx", "ww_idx", "k_idx_norm_scale",
                 "k_idx_norm_bias")
EXPERT_GRADS = ("expert_w1_layer1", "expert_w2_layer1")
GRAD_TOLS = {"lnf_scale": HEAD_GRAD_REL_RMS_TOL,
             "router": ROUTER_GRAD_REL_RMS_TOL,
             **dict.fromkeys(MATRIX_GRADS, MATRIX_GRAD_REL_RMS_TOL),
             **dict.fromkeys(INDEXER_GRADS, INDEXER_GRAD_REL_RMS_TOL),
             **dict.fromkeys(EXPERT_GRADS, EXPERT_GRAD_REL_RMS_TOL),
             **dict.fromkeys(VECTOR_GRADS, VECTOR_GRAD_REL_RMS_TOL)}


# what `transformer.init_params` draws the token embedding at
INIT_STD = 0.02


def build(config, traffic, seed, devices, batches, spans):
    return KeyeJob(config, traffic, seed, devices, batches, spans)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def _kept_sets_f64(reference, terms, top_k, rows):
    """Part (B) for one sequence: of every layer's `rows`, the system's kept
    set (the bits of its packed mask) against the exact top-k of float64 index
    scores on the system's own index queries, keys and weights -> (kept keys
    checked, keys that differ, the largest |I64 - theta64| / max |I64| over
    the differing keys)."""
    checked = differ = 0
    worst = 0.0
    T = terms["kI"].shape[2]
    planes = T // terms["by_query"].shape[-1]
    for layer in range(terms["kI"].shape[0]):
        q = np.asarray(terms["qI"][layer, 0][rows], np.float64)
        w = np.asarray(terms["w"][layer, 0][rows], np.float64)
        k = np.asarray(terms["kI"][layer, 0], np.float64)
        index = reference.index_scores_f64(
            q.reshape(len(rows), w.shape[-1], -1), k, w)         # (R, T)
        words = np.asarray(terms["by_query"][layer, 0][rows])
        ours = (((words[:, None, :] >> np.arange(planes)[:, None]) & 1)
                .reshape(len(rows), T) != 0)
        for r, t in enumerate(rows):
            n = int(min(t + 1, top_k))
            seen = index[r, :t + 1]
            # ties to the lower s: a stable sort of the negated scores
            best = np.argsort(-seen, kind="stable")[:n]
            want = np.zeros(T, bool)
            want[best] = True
            off = ours[r] != want
            checked += n
            differ += int((ours[r] & ~want).sum())
            if off.any():
                theta = seen[best[-1]]
                worst = max(worst, float(
                    np.abs(index[r, off] - theta).max()
                    / max(np.abs(seen).max(), 1e-300)))
    return checked, differ, worst


def _picks_f64(router_in, router, experts):
    """Part (B) for the routers: every layer's picks `experts` (L, S, k)
    against the k largest float64 logits of the router's own input rows
    `router_in` (L, S, D) and weights `router` (L, D, E) -> (picks checked,
    picks that differ, the largest |l64 - theta64| / max |l64| over the
    experts on one side only)."""
    checked = differ = 0
    worst = 0.0
    k = experts.shape[-1]
    for x, w, ours_e in zip(router_in, router, experts):
        logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
        order = np.argsort(-logits, axis=1, kind="stable")
        theta = np.take_along_axis(logits, order[:, k - 1:k], 1)
        want, ours = (np.zeros(logits.shape, bool) for _ in range(2))
        np.put_along_axis(want, order[:, :k], True, 1)
        np.put_along_axis(ours, np.asarray(ours_e), True, 1)
        off = ours != want
        checked += ours_e.size
        differ += int((ours & ~want).sum())
        if off.any():
            worst = max(worst, float((
                np.abs(logits - theta)
                / np.abs(logits).max(1, keepdims=True))[off].max()))
    return checked, differ, worst


class KeyeJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_keye, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_keye.config_from_hf(config, dtype=jnp.bfloat16)
        self.items_per_step = traffic["sequences"] * traffic["seq_len"]

        def init(key):
            params = tfm.init_params(key, cfg)
            # the token embedding at its own scale (config.json `assumed`
            # says why): the initializer's draw, std INIT_STD, scaled
            params["embed"] = params["embed"] * (
                config["assumed"]["embedding_std"] / INIT_STD)
            return params, tfm.init_opt_state(params)

        # weights and optimizer state on the device, in one call
        self.params, self.opt = jax.jit(init)(jax.random.PRNGKey(seed))
        self.member, self.member_shares = self._take_member(
            batches[0]["tokens"])
        self._step = tfm.make_train_step(
            cfg, lr=config["assumed"]["learning_rate"])
        self.batches = batches
        self._i = 0
        self._loss = None
        self._moe = self._dsa = self._traced_picks = None

    def _take_member(self, tokens):
        """WHICH of the group's members this chip is (config.json `assumed`
        says why): the one whose experts take the share of the first batch's
        picks nearest the even one, all layers together. The routers' columns
        are rolled by whole members so that its experts are the ones held;
        every member's columns are the same seeded draw, so the weights are
        as random as they were. -> (member, every member's share)."""
        import jax
        from hetu_tpu.models import transformer as tfm
        cfg = self.cfg
        held, width = cfg.n_experts, cfg.router.width or cfg.n_experts
        picks = np.asarray(jax.jit(lambda p, t: tfm.moe_routing_stats(
            p, t, cfg)["picks"])(self.params,
                                 jax.device_put(tokens, self.device)))
        shares = picks.reshape(len(picks), width // held, held).sum(
            (0, 2)) / picks.sum()
        member = int(np.argmin(np.abs(shares - held / width)))
        # rolled on the host: a program a member would be one compile a
        # member, and the TPU's compiler aborts on the roll by half the
        # width (member 4: `IsFusibleUnalignedDUS`; my chip run, PR 44)
        blocks = self.params["blocks"]
        blocks["router"] = jax.device_put(np.roll(
            np.asarray(blocks["router"]),
            (cfg.router.first_held // held - member) * held, axis=-1),
            self.device)
        return member, shares.tolist()

    def step(self):
        import jax
        with self.spans("feed"):
            batch = jax.device_put(
                self.batches[self._i % len(self.batches)], self.device)
            self._i += 1
        with self.spans("step_call"):
            self._loss, self.params, self.opt = self._step(
                self.params, self.opt, batch["tokens"], batch["targets"])

    def sync(self):
        with self.spans("sync"):
            return float(self._loss)

    def counters(self):
        from benchmark.reduce import dsa
        out = {"flops_per_item": dsa.keye_train_flops_per_token(
            self.config, self.traffic["seq_len"])}
        for name, value in (("moe", self._moe), ("dsa", self._dsa),
                            ("traced_picks", self._traced_picks)):
            if value is not None:
                out[name] = value
        return out

    def _hf_names(self):
        """{a name of GRAD_TOLS: the groups of HF names whose gradients it
        covers}: a group is one leaf of one layer (the held experts'
        matrices of a layer are one); the worst group is reported."""
        from hetu_tpu.models import hf_keye as hk
        cfg = self.cfg
        every = range(cfg.n_layers)
        first = cfg.router.first_held
        experts = lambda i, w: [hk.expert_name(i, first + e, w)
                                for e in range(cfg.n_experts)]
        names = {n: [[hk.hf_name(i, part)] for i in every]
                 for n, part in {**hk.VECTORS, **hk.LINEARS}.items()}
        names.update({"w" + x: [[hk.hf_name(i, part)] for i in every]
                      for x, part in zip("qkv", hk.QKV)})
        names.update(
            lnf_scale=[["model.norm.weight"]],
            expert_w1_layer1=[experts(1, "w1")],
            expert_w2_layer1=[experts(1, "w2")])
        return {n: names[n] for n in GRAD_TOLS}

    def check(self, reference):
        """Part (A): loss, its terms, the residual stream and gradients
        against the float32 reference GIVEN the system's own kept sets and
        picks. Part (B): the kept sets against float64 index scores on the
        system's own index queries, keys and weights, and the routers' picks
        against float64 logits on their own input rows. And the program's
        counters: the pairs kept and L_I a layer on the last step's batch."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.kernels import flash_attention as fa
        from hetu_tpu.models import hf_keye, transformer as tfm
        from benchmark.generators import lm_zipf

        cfg, config = self.cfg, self.config
        self.opt = None        # the job is over: its 4.5 GB are the check's
        sample = jax.device_put(lm_zipf.generate(
            self.traffic, config, self.seed,
            sequences=self.traffic["check_sequences"])[0], self.device)
        tokens, targets = sample["tokens"], sample["targets"]
        T = tokens.shape[1]
        few = min(GRAD_TOKENS, T)
        hf_names = self._hf_names()
        wanted = sorted(h for groups in hf_names.values()
                        for group in groups for h in group)
        one_layer = dataclasses.replace(
            cfg, n_layers=1, layer_types=cfg.layer_types[:1])
        t0 = time.perf_counter()

        # the program's counters, on the batch the last step ran
        last = jax.device_put(
            self.batches[(self._i - 1) % len(self.batches)], self.device)
        counted = jax.device_get(jax.jit(
            lambda p, t: tfm.dsa_stats(p, t, cfg))(self.params,
                                                   last["tokens"]))
        kept_pct = (100.0 * counted["kept"].sum(-1)
                    / (counted["causal"] * last["tokens"].shape[0]))
        self._dsa = {"kept_pairs": counted["kept"].sum(-1).tolist(),
                     "causal_pairs": (counted["causal"]
                                      * last["tokens"].shape[0]).tolist(),
                     "kept_pair_pct": kept_pct.tolist(),
                     "index_loss": counted["loss"].tolist()}
        if self.spans.enabled:
            # the picks of the traced steps' batches, by the weights the
            # window left (no leaf of this model carries a pick counter)
            warm = self.traffic.get("warmup_steps", 3)
            picks_of = jax.jit(lambda p, t: tfm.moe_routing_stats(
                p, t, cfg)["picks"])
            self._traced_picks = [np.asarray(picks_of(
                self.params, jax.device_put(
                    self.batches[i % len(self.batches)]["tokens"],
                    self.device))).tolist()
                for i in range(warm, warm + self.traffic["trace_steps"])]

        # tokens and targets are arguments, not constants of the programs:
        # every seed then reads the same entries of the compile cache. One
        # program a question, run one after another: together their working
        # sets would stand beside each other on a chip the step nearly fills
        def values(params, tokens, targets):
            after_stack, aux = tfm.forward_hidden(params, tokens, cfg)
            after_one, _ = tfm.forward_hidden(
                {**params, "blocks": jax.tree.map(lambda x: x[:1],
                                                  params["blocks"])},
                tokens, one_layer)
            return (tfm.loss_fn(params, tokens, targets, cfg), aux,
                    after_one.astype(jnp.float32),
                    after_stack.astype(jnp.float32))

        def grads(params, tokens, targets):
            loss, g = jax.value_and_grad(tfm.loss_fn)(params, tokens,
                                                      targets, cfg)
            sd = hf_keye.state_dict_from_params(g, cfg)
            return loss, {n: sd[n] for n in wanted}

        routing = jax.jit(lambda p, t: tfm.moe_routing_stats(p, t, cfg))
        routing_terms = jax.jit(lambda p, t: tfm.moe_routing_stats(
            p, t, cfg, terms=True))
        selection = jax.jit(lambda p, t: tfm.dsa_stats(p, t, cfg, terms=True))
        loss, aux, after_one, after_stack = jax.jit(values)(
            self.params, tokens, targets)
        aux, stats = jax.device_get((aux,
                                     routing_terms(self.params, tokens)))
        picks_checked, picks_differ, picks_worst = _picks_f64(
            stats.pop("router_in"),
            jax.device_get(self.params["blocks"]["router"]),
            stats["experts"])
        terms = selection(self.params, tokens)
        self._moe = {"picks": stats["picks"].tolist(),
                     "max_over_mean": stats["max_over_mean"].tolist(),
                     "held": stats["held"].tolist(),
                     "dropped": int(stats["dropped"].sum()),
                     "entropy": stats["entropy"].tolist()}
        # part (B), on the host: rows spread over the sequence, the first
        # top_k (where every key is kept) thinly, the last one always
        rows = np.unique(np.concatenate([
            np.linspace(0, T - 1, CHECK_ROWS).astype(int), [T - 1]]))
        checked, differ, worst = _kept_sets_f64(
            reference, jax.device_get({k: terms[k] for k in (
                "qI", "kI", "w", "by_query")}), cfg.dsa.top_k, rows)
        kept = [fa.unpack_row_mask(m) for m in terms["by_query"]]
        system_index_loss = jax.device_get(terms["loss"])
        del terms
        few_loss, got_grads = jax.device_get(jax.jit(grads)(
            self.params, tokens[:, :few], targets[:, :few]))
        few_picks = routing(self.params, tokens[:, :few])["experts"]
        few_masks = selection(self.params, tokens[:, :few])["by_query"]
        few_kept = [fa.unpack_row_mask(m) for m in few_masks]
        t1 = time.perf_counter()

        sd = hf_keye.state_dict_from_params(self.params, cfg)
        self.params = None     # the reference holds its own (HF) views now
        # part (A): eagerly, the reference jits its layer and head itself
        want_loss, want = reference.loss_terms(
            sd, tokens, targets, config, kept=kept,
            picks=list(jnp.asarray(stats["experts"])))
        hidden_err = {
            "after_layer_0": _rel_rms(after_one, want["hidden"][0]),
            "after_stack": _rel_rms(after_stack, want["hidden"][-1])}
        want_loss, want_terms = jax.device_get(
            (want_loss, {k: want[k] for k in ("balance", "z", "index_loss")}))
        del want, kept, after_one, after_stack
        t2 = time.perf_counter()
        want_few_loss, want_grads = jax.device_get(reference.grads_of(wanted)(
            sd, tokens[:, :few], targets[:, :few], config, kept=few_kept,
            picks=list(few_picks)))
        pooled = lambda g, group: np.concatenate(
            [np.asarray(g[h]).reshape(-1) for h in group])
        grad_err = {n: max(_rel_rms(pooled(got_grads, group),
                                    pooled(want_grads, group))
                           for group in groups)
                    for n, groups in hf_names.items()}
        t3 = time.perf_counter()

        # the counter against its closed form: sum_t min(t + 1, top_k) pairs
        # of T (T + 1) / 2 (23.4 % at 16,384 and 2,048)
        Tb, k = last["tokens"].shape[1], cfg.dsa.top_k
        closed_form = 100.0 * (min(Tb, k) * (min(Tb, k) + 1) // 2
                               + max(Tb - k, 0) * k) / (Tb * (Tb + 1) // 2)
        rel = lambda got, want: abs(float(got) - float(want)) / max(
            abs(float(want)), 1e-30)
        out = {"loss": float(loss), "reference_loss": float(want_loss),
               "loss_abs_err": abs(float(loss) - float(want_loss)),
               "hidden_rel_rms_err": hidden_err,
               "index_loss": system_index_loss.tolist(),
               "reference_index_loss": want_terms["index_loss"].tolist(),
               "index_loss_rel_err": [
                   rel(a, b) for a, b in zip(system_index_loss,
                                             want_terms["index_loss"])],
               "balance_rel_err": rel(aux[0], want_terms["balance"].sum()),
               "z_rel_err": rel(aux[1], want_terms["z"].sum()),
               "kept_pair_pct": self._dsa["kept_pair_pct"],
               "kept_keys_checked": checked,
               "kept_keys_that_differ": differ,
               "kept_differ_share": differ / max(checked, 1),
               "kept_differ_worst_distance": worst,
               "picks_checked": picks_checked,
               "picks_that_differ": picks_differ,
               "picks_differ_share": picks_differ / max(picks_checked, 1),
               "picks_differ_worst_distance": picks_worst,
               "dropped_picks": self._moe["dropped"],
               "member": self.member, "member_shares": self.member_shares,
               "held_picks": self._moe["held"],
               "load_max_over_mean": self._moe["max_over_mean"],
               "grad_tokens": few,
               "grad_loss_abs_err": abs(float(few_loss)
                                        - float(want_few_loss)),
               "grad_rel_rms_err": grad_err,
               "sample": list(tokens.shape),
               "seconds": {"system": t1 - t0, "reference_forward": t2 - t1,
                           "reference_gradients": t3 - t2}}
        out["ok"] = bool(
            np.isfinite(out["loss"])
            and out["loss_abs_err"] <= LOSS_ABS_TOL
            and out["grad_loss_abs_err"] <= GRAD_LOSS_ABS_TOL
            and max(hidden_err.values()) <= HIDDEN_REL_RMS_TOL
            and max(out["index_loss_rel_err"]) <= INDEX_LOSS_REL_TOL
            and max(out["balance_rel_err"], out["z_rel_err"])
            <= ROUTER_LOSS_REL_TOL
            and all(abs(p - closed_form) < 1e-6
                    for p in out["kept_pair_pct"])
            and out["kept_differ_share"] <= KEPT_DIFFER_MAX_SHARE
            and out["kept_differ_worst_distance"] <= NEAR_THRESHOLD_REL
            and out["picks_differ_share"] <= PICKS_DIFFER_MAX_SHARE
            and out["picks_differ_worst_distance"] <= NEAR_PICK_REL
            and out["dropped_picks"] == 0
            and set(grad_err) == set(GRAD_TOLS)
            and all(err <= GRAD_TOLS[n] for n, err in grad_err.items()))
        return out

    def close(self):
        pass
