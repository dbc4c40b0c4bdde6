"""olmoe-1b-7b as a user's job script builds it: the published config.json
through `hf_olmoe.config_from_hf`, weights from the program's own
initialiser, `transformer.make_train_step` (next-token cross-entropy, the
load-balancing and router z losses, AdamW in the step). Only architecture,
shapes, optimizer and compute dtype are stated; attention implementation,
fused cross-entropy, recomputation, the grouped matmul and kernel mode stay
the program's defaults.
"""
import time

import numpy as np

# Agreement with the float32 reference (reference.py) on the correctness
# sample (one sequence of 4,096 tokens, another stream of the same seed)
# with the weights the window left. The system computes in bfloat16 (8 bits
# of mantissa) with float32 accumulation, router and losses; the reference
# in float32 at "highest" precision. Measured on the v5e over 13 runs of
# both sides, 13 seeds, after 39-44 steps (my chip runs, PR 25): CE differs
# by 6e-5..1.3e-3 of ~6.8, the balance term by 2e-5..2.3e-4 of its value
# (27-34: the routers collapse in 40 steps at lr 4e-4 without warm-up), the
# z term by <= 3e-5 of its value, final hidden states by 0.32-0.92 % of
# their RMS, 98.7-99.8 % of the (token, pick) pairs choose an expert the
# reference chooses too (a near-tie in 64 probabilities flips on bfloat16
# inputs), and the gradients of the six small tensors by 0.3-4.5 % of their
# RMS (router 4.5 %, ln2 4.1 %, q_norm 3.6 %, k_norm 3.6 %, ln1 3.5 %, lnf
# 0.9 % at most; a flipped pick changes a token's whole backward path).
# Each bound sits 3-4x above the largest seen (a share: below the
# smallest). An fp8 expert matmul (3 bits of mantissa, ~16x coarser than
# bfloat16) fails the hidden-state bound; a dropped pick or normalised top-k
# weights move the hidden states by tens of percent; a loss term missing
# from `loss_fn` moves CE, which is read as the loss less the weighted
# terms, by 0.1-0.3.
HIDDEN_REL_RMS_TOL = 3e-2
CE_ABS_TOL = 5e-3
BALANCE_REL_TOL = 1e-3
Z_REL_TOL = 2e-4
SAME_EXPERT_MIN_SHARE = 0.955
GRAD_REL_RMS_TOL = 0.15
# small tensors whose gradients see the whole backward path: through the
# combine weights, the experts, attention and both norms (five of a block,
# then the final norm's)
GRAD_NAMES = ("router", "q_norm", "k_norm", "ln1_scale", "ln2_scale",
              "lnf_scale")


def build(config, traffic, seed, devices, batches, spans):
    return OlmoeJob(config, traffic, seed, devices, batches, spans)


_HF_OF = {"router": "mlp.gate", "q_norm": "self_attn.q_norm",
          "k_norm": "self_attn.k_norm", "ln1_scale": "input_layernorm",
          "ln2_scale": "post_attention_layernorm"}


def _hf_names(name, layers):
    """The HF names of one of `GRAD_NAMES`, layer by layer."""
    if name == "lnf_scale":
        return ["model.norm.weight"]
    return [f"model.layers.{i}.{_HF_OF[name]}.weight" for i in range(layers)]


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


class OlmoeJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_olmoe, transformer as tfm

        self.config, self.traffic, self.seed = config, traffic, seed
        self.device, self.spans = devices[0], spans
        self.cfg = cfg = hf_olmoe.config_from_hf(config, dtype=jnp.bfloat16)
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

    def sync(self):
        with self.spans("sync"):
            return float(self._loss)

    def counters(self):
        from benchmark.reduce import moe
        c = self.config
        out = {"flops_per_item": moe.olmoe_train_flops_per_token(
            c["hidden_size"], c["num_hidden_layers"], c["intermediate_size"],
            c["num_experts"], c["num_experts_per_tok"], c["vocab_size"],
            self.traffic["seq_len"])}
        if self._moe is not None:
            out["moe"] = self._moe
        return out

    def check(self, reference):
        """The system's loss terms, final hidden states, routing and the
        gradients of `GRAD_NAMES` on a seeded sample against the float32
        reference, which is handed the same weights under their HF names."""
        import jax
        import jax.numpy as jnp
        from hetu_tpu.models import hf_olmoe, transformer as tfm
        from benchmark.generators import lm_zipf

        cfg, config = self.cfg, self.config
        self.opt = None        # the job is over: its 5 GB are the reference's
        sample = jax.device_put(lm_zipf.generate(
            self.traffic, config, self.seed,
            sequences=self.traffic["check_sequences"])[0], self.device)
        tokens, targets = sample["tokens"], sample["targets"]
        weights = tfm.aux_weights()
        t0 = time.perf_counter()

        # tokens and targets are arguments, not constants of the programs:
        # every seed then reads the same two entries of the compile cache
        def system(params, tokens, targets):
            def loss_of(small_blocks, lnf_scale):
                blocks = {**params["blocks"], **small_blocks}
                return tfm.loss_fn(
                    {**params, "lnf_scale": lnf_scale, "blocks": blocks},
                    tokens, targets, cfg)

            loss, (g_blocks, g_lnf) = jax.value_and_grad(loss_of, (0, 1))(
                {n: params["blocks"][n] for n in GRAD_NAMES[:-1]},
                params["lnf_scale"])
            hidden, aux = tfm.forward_hidden(params, tokens, cfg)
            stats = tfm.moe_routing_stats(params, tokens, cfg)
            return (loss, aux, hidden.astype(jnp.float32),
                    {**g_blocks, "lnf_scale": g_lnf}, stats)

        loss, aux, hidden, grads, stats = jax.device_get(
            jax.jit(system)(self.params, tokens, targets))
        t1 = time.perf_counter()
        ce = float(loss) - float(np.dot(jax.device_get(weights), aux))
        self._moe = {"picks": stats["picks"].tolist(),
                     "max_over_mean": stats["max_over_mean"].tolist(),
                     "dropped": int(stats["dropped"].sum()),
                     "entropy": stats["entropy"].tolist()}

        sd = hf_olmoe.state_dict_from_params(self.params, cfg)
        hf_names = {n: _hf_names(n, cfg.n_layers) for n in GRAD_NAMES}

        def the_reference(sd, tokens, targets):
            _loss, terms = reference.loss_and_hidden(sd, tokens, targets,
                                                     config)
            grads = reference.grads_of(sorted(sum(hf_names.values(), [])))(
                sd, tokens, targets, config)
            return {k: terms[k] for k in ("ce", "balance", "z", "hidden",
                                          "experts")}, grads

        # one program: the compiler shares the forward pass of the two
        want, want_grads = jax.device_get(
            jax.jit(the_reference)(sd, tokens, targets))
        t2 = time.perf_counter()
        # into the system's layout: stacked on a layer axis, the router
        # (in, out)
        want_small = {
            n: want_grads[names[0]] if n == "lnf_scale" else np.stack(
                [want_grads[h].T if n == "router" else want_grads[h]
                 for h in names])
            for n, names in hf_names.items()}

        same = np.mean(np.any(
            stats["experts"][..., :, None] == want["experts"][..., None, :],
            -1))
        out = {"loss": float(loss),
               "ce_abs_err": abs(ce - float(want["ce"])),
               "balance_rel_err": abs(float(aux[0]) - float(want["balance"]))
               / max(abs(float(want["balance"])), 1e-30),
               "z_rel_err": abs(float(aux[1]) - float(want["z"]))
               / max(abs(float(want["z"])), 1e-30),
               "reference": {k: float(want[k])
                             for k in ("ce", "balance", "z")},
               "hidden_rel_rms_err": _rel_rms(hidden, want["hidden"]),
               "same_expert_share": float(same),
               "grad_rel_rms_err": {n: _rel_rms(grads[n], want_small[n])
                                    for n in GRAD_NAMES},
               "dropped_picks": self._moe["dropped"],
               "sample": list(tokens.shape),
               "seconds": {"system": t1 - t0, "reference": t2 - t1}}
        out["ok"] = bool(
            np.isfinite(out["loss"])
            and out["ce_abs_err"] <= CE_ABS_TOL
            and out["balance_rel_err"] <= BALANCE_REL_TOL
            and out["z_rel_err"] <= Z_REL_TOL
            and out["hidden_rel_rms_err"] <= HIDDEN_REL_RMS_TOL
            and out["same_expert_share"] >= SAME_EXPERT_MIN_SHARE
            and max(out["grad_rel_rms_err"].values()) <= GRAD_REL_RMS_TOL
            and out["dropped_picks"] == 0)
        return out

    def close(self):
        pass
