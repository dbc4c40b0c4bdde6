"""bert-base as a user's job script builds it: `BertConfig.hf()` at the
published sizes, `bert.make_pretrain_step` (MLM + NSP, AdamW in the step),
optionally over a dp mesh. Only architecture, shapes, optimizer and layout
are stated; attention implementation, fused CE, recomputation and kernel
mode stay the program's defaults.
"""
import numpy as np

# Agreement with the float32 reference (reference.py), on the correctness
# sample, with the weights the window left behind. The system computes in
# bfloat16 (8 bits of mantissa) with float32 accumulation, the reference in
# float32 at "highest" precision. Measured on the v5e (my chip runs, PR 22,
# 14 runs over the bert-base cells): final hidden states differ by
# 1.01-1.06 % of their RMS in every run, the MLM loss by 1.5e-4..2.9e-3.
# The NSP loss is the mean over 2 to 4 sequences of a head that some tens
# of steps on random labels have driven to large logits (NSP 0.5 to 3), and
# its error grows with it: 3.5e-4..3.1e-2, at most 1 % of the value. The
# bounds sit ~3-4x above the largest seen. An fp8 matmul path (3 bits of
# mantissa) is ~16x coarser than bfloat16 and fails the hidden-state bound;
# a dropped loss term (NSP is >= 0.5 of ~11) fails the loss bounds.
HIDDEN_REL_RMS_TOL = 4e-2
MLM_LOSS_ABS_TOL = 1e-2
NSP_LOSS_ABS_TOL = 1e-2      # plus NSP_LOSS_REL_TOL of the reference's NSP
NSP_LOSS_REL_TOL = 3e-2


def build(config, traffic, seed, devices, batches, spans):
    return BertJob(config, traffic, seed, devices, batches, spans)


class BertJob:
    def __init__(self, config, traffic, seed, devices, batches, spans):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from hetu_tpu.models import bert
        from hetu_tpu.parallel import mesh as meshlib

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self.cfg = cfg = bert.BertConfig.hf(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            n_heads=config["num_attention_heads"],
            n_layers=config["num_hidden_layers"],
            d_ff=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            type_vocab_size=config["type_vocab_size"], dtype=jnp.bfloat16)
        layout = traffic.get("mesh")
        if layout and int(np.prod(list(layout.values()))) != len(devices):
            raise ValueError(f"mesh {layout} does not fill {len(devices)} "
                             "device(s)")
        self.mesh = mesh = (meshlib.make_mesh(**layout, devices=devices)
                            if layout else None)
        self.items_per_step = traffic["sequences"] * traffic["seq_len"]

        def init(key):
            params = bert.init_params(key, cfg)
            return params, bert.init_opt_state(params)

        if mesh is None:
            self.batch_sharding = devices[0]
            init = jax.jit(init)
            self.param_sharding = None
        else:
            specs = bert.param_specs(cfg)
            pshard = jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            self.param_sharding = pshard
            self.batch_sharding = NamedSharding(mesh, P(("dp",)))
            init = jax.jit(init, out_shardings=(
                pshard, {"m": pshard, "v": pshard,
                         "t": NamedSharding(mesh, P())}))
        # weights and optimizer state on the device, in one call
        self.params, self.opt = init(jax.random.PRNGKey(seed))
        self._step = bert.make_pretrain_step(
            cfg, mesh=mesh, lr=config["assumed"]["learning_rate"])
        self.batches = batches
        self._i = 0
        self._loss = None

    def step(self):
        import jax
        with self.spans("feed"):
            batch = jax.device_put(
                self.batches[self._i % len(self.batches)],
                self.batch_sharding)
            self._i += 1
        with self.spans("step_call"):
            self._loss, _parts, self.params, self.opt = self._step(
                self.params, self.opt, batch)

    def sync(self):
        with self.spans("sync"):
            return float(self._loss)

    def counters(self):
        from benchmark.reduce import flops
        c, t = self.config, self.traffic
        return {"flops_per_item": flops.bert_pretrain_flops_per_token(
            c["hidden_size"], c["num_hidden_layers"], c["intermediate_size"],
            c["vocab_size"], t["seq_len"], t["predictions"])}

    def check(self, reference):
        """The system's losses and final hidden states on a seeded sample,
        through the cell's own layout, against the float32 reference."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from hetu_tpu.models import bert
        from benchmark.generators import mlm_pretrain

        cfg, mesh = self.cfg, self.mesh
        sample = mlm_pretrain.generate(
            self.traffic, self.config, self.seed + 1,
            sequences=self.traffic["check_sequences"])[0]

        def system(params, b):
            loss, (mlm, nsp) = bert.pretrain_loss(params, b, cfg, mesh)
            hidden = bert.encode(params, b["input_ids"], b["segment_ids"],
                                 cfg, mesh, b["input_mask"])
            return loss, mlm, nsp, hidden.astype(jnp.float32)

        if mesh is None:
            system = jax.jit(system)
        else:
            rep = NamedSharding(mesh, P())
            system = jax.jit(system,
                             in_shardings=(self.param_sharding,
                                           self.batch_sharding),
                             out_shardings=rep)
        got = jax.device_get(system(
            self.params, jax.device_put(sample, self.batch_sharding)))
        one = self.devices[0]
        want = jax.device_get(reference.loss_and_hidden(
            jax.device_put(self.params, one), jax.device_put(sample, one),
            n_heads=self.config["num_attention_heads"],
            eps=self.config["layer_norm_eps"]))
        real = np.asarray(sample["input_mask"], bool)
        diff = (np.asarray(got[3]) - np.asarray(want[3]))[real]
        hidden_rel = float(np.sqrt(np.mean(diff ** 2))
                           / np.sqrt(np.mean(np.asarray(want[3])[real] ** 2)))
        out = {"loss": float(got[0]), "reference_loss": float(want[0]),
               "mlm_abs_err": abs(float(got[1]) - float(want[1])),
               "nsp_abs_err": abs(float(got[2]) - float(want[2])),
               "reference_nsp": float(want[2]),
               "hidden_rel_rms_err": hidden_rel,
               "sample": list(sample["input_ids"].shape)}
        out["ok"] = bool(
            np.isfinite(out["loss"])
            and out["mlm_abs_err"] <= MLM_LOSS_ABS_TOL
            and out["nsp_abs_err"] <= (NSP_LOSS_ABS_TOL
                                       + NSP_LOSS_REL_TOL * abs(want[2]))
            and hidden_rel <= HIDDEN_REL_RMS_TOL)
        return out

    def close(self):
        pass
