"""chip_smoke.py's contract off the chip: it refuses a non-TPU backend in
one line, and its phase functions run at tiny sizes under the CPU pin
(``chip=False`` drops the checks only a TPU can meet; the kernels run in
interpret mode), so the chip run is never the first time a phase's Python
executes."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_refuses_cpu_backend_in_one_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""            # no result line, no phase line
    lines = [ln for ln in p.stderr.strip().splitlines() if ln.strip()]
    assert len(lines) == 1 and "device phase failed" in lines[0]
    assert "'cpu'" in lines[0]               # names the platform it found


def test_executor_phase_tiny(capsys):
    rec = chip_smoke.phase_executor(batch=8, steps=8, n_batches=2,
                                    chip=False)
    line = _last_json(capsys)
    assert line["phase"] == "executor" and line["platform"] == "cpu"
    assert rec["last_loss"] < rec["first_loss"]
    # under the CPU pin auto declines for the backend, and says so
    assert any("backend" in k for k in rec["dispatch"]["fallback_reasons"])


def test_flagship_phase_tiny(capsys):
    from hetu_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=512, d_model=64, n_heads=4, n_layers=2,
                          d_ff=128, max_seq_len=128, dtype=jnp.float32,
                          remat=False)
    moe = dict(chip_smoke.MOE_ROW, vocab_size=64, d_model=64, n_heads=4,
               d_ff=32, max_seq_len=32, n_experts=8, n_experts_per_tok=2,
               dtype=jnp.float32)
    mla = dict(chip_smoke.MLA_ROW, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=24, n_routed_experts=2,
               num_routed_experts=8, first_expert_held=2,
               num_experts_per_tok=2, vocab_size=64,
               max_position_embeddings=32, dtype=jnp.float32)
    dsa = dict(chip_smoke.DSA_ROW, hidden_size=32, intermediate_size=64,
               moe_intermediate_size=24, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_experts=2,
               num_routed_experts=8, first_expert_held=2,
               num_experts_per_tok=2, vocab_size=64,
               max_position_embeddings=32, dtype=jnp.float32,
               sa_config=dict(indexer_num_heads=4, indexer_head_dim=8,
                              indexer_num_kv_heads=1, topk=8))
    kda = dict(chip_smoke.KDA_ROW, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=24, num_experts=2,
               num_routed_experts=8, first_expert_held=2,
               num_experts_per_token=2, vocab_size=64, model_max_length=40,
               linear_attn_config=dict(
                   kda_layers=[1], full_attn_layers=[2], num_heads=4,
                   head_dim=16, short_conv_kernel_size=4),
               kda_chunk=16, dtype=jnp.float32)
    gdn = dict(chip_smoke.GDN_ROW, hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, shared_expert_intermediate_size=24,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               num_experts=2, num_routed_experts=8, first_expert_held=2,
               num_experts_per_tok=3, vocab_size=64,
               max_position_embeddings=40, gdn_chunk=16, dtype=jnp.float32)
    rec = chip_smoke.phase_flagship(cfg=cfg, batch=4, seq=128, n_pred=8,
                                    steps=3, chip=False, moe=moe, mla=mla,
                                    mla_batch=2, dsa=dsa, dsa_batch=2,
                                    kda=kda, kda_batch=2, gdn=gdn,
                                    gdn_batch=2)
    line = _last_json(capsys)
    assert line["phase"] == "flagship"
    assert (line["dsa"]["heads"], line["dsa"]["kv_heads"],
            line["dsa"]["head_dim"], line["dsa"]["top_k"]) == (4, 2, 16, 8)
    assert line["dsa"]["kept_pairs"] == 2 * (36 + 24 * 8)
    assert line["dsa"]["index_loss"] > 0
    assert abs(line["dsa"]["loss"] - line["dsa"]["dot_loss"]) < 1e-4
    # 40 positions in chunks of 16: the last chunk is not whole
    assert (line["kda"]["heads"], line["kda"]["head_dim"],
            line["kda"]["chunk"], line["kda"]["rotate"]) == (4, 16, 16, False)
    assert line["kda"]["scan_rel_rms_err_vs_f64"] < 1e-5
    assert line["kda"]["chunk_log_decay_min"] < 0
    assert line["kda"]["dropped_picks"] == 0 and line["kda"]["tokens"] == 80
    # the XLA form off the chip in both passes, g broadcast, 16 not dividing
    # 40; 4 heads of 32 on 2, a quarter of a head rotated
    assert (line["gdn"]["key_heads"], line["gdn"]["value_heads"],
            line["gdn"]["head_dim"], line["gdn"]["chunk"]) == (2, 4, 16, 16)
    assert set(line["gdn"]["scan_rel_rms_err_vs_f64"]) == {"step", "xla"}
    assert line["gdn"]["scan_served_by"] == {"step": ["xla"], "xla": ["xla"]}
    assert max(line["gdn"]["scan_rel_rms_err_vs_f64"].values()) < 1e-5
    assert line["gdn"]["chunk_log_decay_min"] < 0
    assert line["gdn"]["attn_heads"] == [4, 2, 32, 8]
    assert line["gdn"]["dropped_picks"] == 0 and line["gdn"]["tokens"] == 80
    assert line["mla"]["dropped_picks"] == 0 and line["mla"]["tokens"] == 64
    assert (line["mla"]["qk_dim"], line["mla"]["v_dim"],
            line["mla"]["d_ff_shared"]) == (48, 24, 64)
    assert abs(line["mla"]["loss"] - line["mla"]["dot_loss"]) < 1e-4
    assert 0 < line["mla"]["held_picks"] < 64 * 2
    assert line["moe"]["dropped_picks"] == 0
    assert line["moe"]["tokens"] == 64 and line["moe"]["experts"] == 8
    assert max(line["moe"]["rel_rms_err"].values()) < 1e-4
    assert rec["attn_impl"] == "dot" and rec["mlm_ce"] == "einsum"
    # the CPU reports no memory limit: the checkpoint keeps nothing
    assert line["remat"] == {"names": [], "held_bytes": 0, "budget_bytes": 0}
    assert abs(rec["vs_reference"]["loss"]
               - rec["vs_reference"]["reference_loss"]) < 1e-3


def test_ps_phase_tiny(capsys):
    rec = chip_smoke.phase_ps(batch=16, steps=6, feature_dim=1000,
                              embedding_size=16, chip=False)
    assert _last_json(capsys)["phase"] == "ps"
    assert rec["pushes_ok"] == rec["server_updates"] > 0


def test_kernels_phase_tiny(capsys):
    rec = chip_smoke.phase_kernels(chip=False, shapes={
        "flash": (1, 2, 128, 32), "fused_ce": (32, 64, 300),
        "flash_bwd_dqkv": {"two-widths": (1, 2, 1024, 192, 128),
                           "pairs-of-64": (2, 4, 1024, 64, 64)},
        "flash_window": {"a-window-of-100": (1, 2, 512, 128, 100)},
        "embed_grad": (128, 128, 1000), "csr_spmm": (300, 16, 16, 128),
        "quant": (4096, 256), "opt": (300, 700),
        "rope": (1, 32, 2, 128, 64),
        "rope_halves": {"half-a-head-under-yarn": (1, 32, 4, 2, 128, 64, True),
                        "heads-of-64": (2, 32, 4, 2, 64, 0, False)},
        "ssd": (1, 256, 2, 64, 1, 128, 128),
        "ssd_groups": (1, 256, 16, 64, 2, 128, 128),
        "grouped_matmul": (96, 384, 192, (10, 0, 50, 20))})
    assert _last_json(capsys)["phase"] == "kernels"
    assert set(rec["kernels"]) == {
        "flash_causal", "flash_key_padding", "fused_ce", "fused_embed_grad",
        "csr_spmm", "quant_blocks", "dequant_blocks", "fused_adam",
        "fused_sgd", "rope_pairs", "flash_bwd_dqkv:two-widths",
        "flash_bwd_dqkv:pairs-of-64", "flash_window:a-window-of-100",
        "rope_halves:half-a-head-under-yarn", "rope_halves:heads-of-64",
        "ssd", "ssd_groups", "grouped_matmul"}
