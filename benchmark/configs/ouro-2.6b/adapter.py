"""ouro-2.6b as a user's job script builds it: the published config.json
through `hf_ouro.config_from_hf`, weights from the program's own
initialiser, `transformer.make_train_step` (the expected next-token loss
over the four exits less the entropy bonus, AdamW in the step). Only
architecture, shapes, optimizer and compute dtype are stated; attention
implementation, fused cross-entropy, recomputation and kernel mode stay the
program's defaults.
"""
import time

import numpy as np

# Agreement with the float32 reference (reference.py) on the correctness
# sample (one sequence of 4,096 tokens, another stream of the same seed)
# with the weights the window left. The system computes the trunk and the
# head in bfloat16 (8 bits of mantissa) with float32 accumulation; the exit
# gate, the exit distribution q, the entropy and the losses are float32 in
# both; the reference is float32 at "highest" precision throughout.
# Measured on the v5e over 27 runs of both sides, 24 seeds, after 37-44 steps
# (my chip runs, PR 29), measured -> bound, each bound 3-5x above the largest
# seen. In 40 steps at lr 3e-4 without warm-up the gate goes where the seed
# sends it: q ends anywhere from (1, 1e-8, 0, 0) through (0.3, 0.2, 0.1, 0.3)
# to (0, 0, 0, 1), so every bound has to hold across that whole range.
EXIT_REL_RMS_TOL = 1.5e-2    # each exit's state, of its RMS: 0.39-0.47 %
NLL_ABS_TOL = 4e-3           # each exit's mean NLL, of ~7.3: <= 1.04e-3
LOSS_ABS_TOL = 4e-3          # <= 1.10e-3
Q_ABS_TOL = 3e-3             # mean |q - q_ref| over exits, tokens: <= 8.1e-4
Q_SUM_ABS_TOL = 5e-4         # |sum_t q(t) - 1|, the worst token: <= 1.42e-4
                             # (the TPU's float32 exp and log1p, not the sum)
# The two that hold float32 to float32 WHATEVER the bfloat16 trunk did and
# wherever the gate went: the published gate and q in numpy float64 from the
# system's OWN exit states against the system's log q (3.4e-6 to 1.94e-4:
# float32 accumulation over 2,048 terms and the TPU's transcendentals), and
# the weighting and entropy in float64 from the system's own q, log q and NLL
# against its loss (7.6e-8 to 6.8e-7). One bfloat16 pass where float32 is
# stated fails them (my chip runs, PR 29, the job's own check with one part
# patched, at a saturated gate, seed 3000000101, and an unsaturated one, seed
# 41: the gate matvec in bfloat16 reads 2.6e-2 / 1.6e-2, log q rounded to
# bfloat16 3.1e-2 / 1.6e-2, the weighting in bfloat16 9.1e-3 / 1.4e-2 on the
# loss), where the comparisons with the reference above cannot always tell:
# the exit states the gate reads are bfloat16 values to begin with, and a
# saturated q hides its logit.
OWN_LOG_Q_ABS_TOL = 1e-3
OWN_LOSS_ABS_TOL = 1e-5
# gradients, of the reference's RMS: the five norm scales <= 6.4 %, layer 0's
# q|k|v <= 7.7 %. The gate's two are differences of the exits' NLLs, which
# differ by less than bfloat16 moves them: 0.02-1.2 % on 25 runs, 11.1 % on
# both runs of one seed (q = 0.31, 0.21, 0.14, 0.34); and exactly zero in the
# reference where its q underflows (q = 1, 9e-9, 0, 0), so their RMS has a floor
GRAD_REL_RMS_TOL = 0.25
GATE_GRAD_REL_RMS_TOL = 0.4
GATE_GRAD_RMS_FLOOR = 1e-6
# tensors whose gradients see the whole backward path: the gate's two, the
# four norm scales of a block and the final norm's, and layer 0's fused
# q|k|v projection (its gradient sums four passes)
GATE = ("exit_gate_w", "exit_gate_b")
SMALL_TOP = GATE + ("lnf_scale",)
SMALL_BLOCK = ("ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale")
GRAD_NAMES = SMALL_TOP + SMALL_BLOCK + ("wqkv_layer0",)


def build(config, traffic, seed, devices, batches, spans):
    return OuroJob(config, traffic, seed, devices, batches, spans)


def _own_terms_f64(exits, gate_w, gate_b, q, log_q, nll, beta):
    """In numpy float64 -> (log q by the published gate and exit
    distribution from the SYSTEM's own exit states, the loss by the published
    weighting from its own q, log q and per-token NLL): what the system's
    float32 gate, q, entropy and weighting must reproduce to float32
    rounding, whatever its bfloat16 trunk did before them."""
    f64 = lambda x: np.asarray(x, np.float64)
    z = f64(exits) @ f64(gate_w) + float(gate_b)
    log_go = -np.logaddexp(0.0, z)
    passed = np.cumsum(log_go, 0) - log_go
    own_log_q = np.concatenate(
        [(passed - np.logaddexp(0.0, -z))[:-1], passed[-1:]], 0)
    loss = np.mean(np.sum(f64(q) * f64(nll) + beta * f64(q) * f64(log_q), 0))
    return own_log_q, float(loss)


def _rel_rms(got, want, floor=1e-30):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), floor))


class OuroJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_ouro, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_ouro.config_from_hf(config, dtype=jnp.bfloat16)
        self.items_per_step = traffic["sequences"] * traffic["seq_len"]

        def init(key):
            params = tfm.init_params(key, cfg)
            return params, tfm.init_opt_state(params)

        # weights and optimizer state on the device, in one call
        self.params, self.opt = jax.jit(init)(jax.random.PRNGKey(seed))
        self._step = tfm.make_train_step(
            cfg, lr=config["assumed"]["learning_rate"])
        self.batches = batches
        self._i = 0
        self._loss = None
        self._loop = None

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
        from benchmark.reduce import loop
        c = self.config
        out = {"flops_per_item": loop.looped_train_flops_per_token(
            c["hidden_size"], c["num_hidden_layers"], c["intermediate_size"],
            c["vocab_size"], self.traffic["seq_len"], c["total_ut_steps"])}
        if self._loop is not None:
            out["loop"] = self._loop
        return out

    def check(self, reference):
        """The system's exit NLLs, exit distribution, loss, exit states and
        the gradients of `GRAD_NAMES` on a seeded sample against the float32
        reference, which is handed the same weights under their HF names."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_ouro, transformer as tfm
        from benchmark.generators import lm_zipf

        cfg, config = self.cfg, self.config
        self.opt = None        # the job is over: its 4 GB are the reference's
        sample = jax.device_put(lm_zipf.generate(
            self.traffic, config, self.seed,
            sequences=self.traffic["check_sequences"])[0], self.device)
        tokens, targets = sample["tokens"], sample["targets"]
        t0 = time.perf_counter()

        # tokens and targets are arguments, not constants of the program:
        # every seed then reads the same entry of the compile cache
        def system(params, tokens, targets):
            def loss_of(small, wqkv0):
                blocks = {**params["blocks"],
                          **{n: small[n] for n in SMALL_BLOCK},
                          "wqkv": params["blocks"]["wqkv"].at[0].set(wqkv0)}
                return tfm.exit_loss_terms(
                    {**params, **{n: small[n] for n in SMALL_TOP},
                     "blocks": blocks}, tokens, targets, cfg)

            small = {n: params[n] for n in SMALL_TOP}
            small.update({n: params["blocks"][n] for n in SMALL_BLOCK})
            (loss, terms), (g_small, g_wqkv0) = jax.value_and_grad(
                loss_of, (0, 1), has_aux=True)(small,
                                               params["blocks"]["wqkv"][0])
            stats = tfm.exit_stats(params, tokens, cfg)
            return (loss, terms["nll"], terms["q"], terms["log_q"],
                    terms["exits"].astype(jnp.float32),
                    {**g_small, "wqkv_layer0": g_wqkv0}, stats)

        loss, nll, q, log_q, exits, grads, stats = jax.device_get(
            jax.jit(system)(self.params, tokens, targets))
        own_log_q, own_loss = _own_terms_f64(
            exits, *jax.device_get((self.params["exit_gate_w"],
                                    self.params["exit_gate_b"])),
            q, log_q, nll, config["assumed"]["exit_entropy_weight"])
        t1 = time.perf_counter()
        self._loop = {"q_mean": stats["q_mean"].tolist(),
                      "expected_exit_step": float(
                          stats["expected_exit_step"])}

        sd = hf_ouro.state_dict_from_params(self.params, cfg)
        self.params = None     # the reference holds its own (HF) views now
        L = cfg.n_layers
        hf_names = {
            "exit_gate_w": [hf_ouro.GATE_W], "exit_gate_b": [hf_ouro.GATE_B],
            "lnf_scale": ["model.norm.weight"],
            **{n: [hf_ouro.hf_name(i, hf_ouro.NORMS[n]) for i in range(L)]
               for n in SMALL_BLOCK},
            "wqkv_layer0": [hf_ouro.hf_name(0, f"self_attn.{p}_proj")
                            for p in "qkv"]}
        # eagerly: the reference jits its block and its exit head itself
        want_loss, want = reference.loss_terms(sd, tokens, targets, config)
        want_grads = reference.grads_of(sorted(sum(hf_names.values(), [])))(
            sd, tokens, targets, config)
        want_loss, want, want_grads = jax.device_get(
            (want_loss, {k: want[k] for k in ("nll", "q", "exits")},
             want_grads))
        t2 = time.perf_counter()
        # into the system's layout: stacked on a layer axis, q|k|v (in, out)
        want_small = {n: np.stack([want_grads[h] for h in names])
                      for n, names in hf_names.items() if n in SMALL_BLOCK}
        want_small.update(
            exit_gate_w=want_grads[hf_ouro.GATE_W].reshape(-1),
            exit_gate_b=want_grads[hf_ouro.GATE_B].reshape(()),
            lnf_scale=want_grads["model.norm.weight"],
            wqkv_layer0=np.concatenate(
                [want_grads[h].T for h in hf_names["wqkv_layer0"]], 1))

        out = {"loss": float(loss),
               "loss_abs_err": abs(float(loss) - float(want_loss)),
               "nll_abs_err": [abs(float(a.mean()) - float(b.mean()))
                               for a, b in zip(nll, want["nll"])],
               "q_mean_abs_err": float(np.mean(np.abs(q - want["q"]))),
               "q_sum_abs_err": float(np.max(np.abs(q.sum(0) - 1.0))),
               "own_log_q_abs_err": float(np.max(np.abs(log_q - own_log_q))),
               "own_loss_abs_err": abs(float(loss) - own_loss),
               "exit_rel_rms_err": [_rel_rms(a, b)
                                    for a, b in zip(exits, want["exits"])],
               "grad_rel_rms_err": {
                   n: _rel_rms(grads[n], want_small[n],
                               GATE_GRAD_RMS_FLOOR if n in GATE else 1e-30)
                   for n in GRAD_NAMES},
               "reference": {"loss": float(want_loss),
                             "nll": [float(x.mean()) for x in want["nll"]],
                             "q_mean": want["q"].mean((1, 2)).tolist()},
               "q_mean": self._loop["q_mean"],
               "expected_exit_step": self._loop["expected_exit_step"],
               "sample": list(tokens.shape),
               "seconds": {"system": t1 - t0, "reference": t2 - t1}}
        out["ok"] = bool(
            np.isfinite(out["loss"])
            and out["loss_abs_err"] <= LOSS_ABS_TOL
            and max(out["nll_abs_err"]) <= NLL_ABS_TOL
            and out["q_mean_abs_err"] <= Q_ABS_TOL
            and out["q_sum_abs_err"] <= Q_SUM_ABS_TOL
            and out["own_log_q_abs_err"] <= OWN_LOG_Q_ABS_TOL
            and out["own_loss_abs_err"] <= OWN_LOSS_ABS_TOL
            and max(out["exit_rel_rms_err"]) <= EXIT_REL_RMS_TOL
            and all(err <= (GATE_GRAD_REL_RMS_TOL if n in GATE
                            else GRAD_REL_RMS_TOL)
                    for n, err in out["grad_rel_rms_err"].items()))
        return out

    def close(self):
        pass
