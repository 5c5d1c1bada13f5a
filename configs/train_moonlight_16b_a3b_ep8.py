# Moonshot's Moonlight-16B-A3B (HF model_type deepseek_v3) as ONE CHIP'S SHARE
# of an 8-way expert-parallel pretraining job, on one v5e chip (16 GB):
#
#   python -m nanosandbox_tpu.data.prepare english_prose_bpe --fold_vocab=20480
#   python -m nanosandbox_tpu.train configs/train_moonlight_16b_a3b_ep8.py
#
# Published widths (huggingface.co/moonshotai/Moonlight-16B-A3B config.json):
# hidden 2048, latent attention with 16 heads (a query / key head of 128
# content + 64 rotary dims, the rotary key shared by all heads; value heads
# of 128; keys and values from a latent of 512; rotary theta 50000), dense
# width 11264 in the first layer, then 64 routed experts of width 1408, 6 a
# token (sigmoid scores, a selection bias, weights normalised and scaled by
# 2.446) beside a shared expert of 2 x 1408, untied head. Cut to the chip
# (chipbench/configs/moonlight-16b-a3b-ep8.json says why, key by key): the
# dense layer and five expert layers of 27, experts 0..7 of every expert
# layer (rank 0 of 8; the router still scores all 64 and what the absent
# experts would add is left out), rows 0..20479 of the vocabulary (ids folded
# into the slice by the preparer). 668.9 M parameters, 10.7 GB of parameters,
# gradients and Adam state; remat for the rest. The published model was
# trained with Muon; this trainer has AdamW.
out_dir = "runs/moonlight_16b_a3b_ep8"
dataset = "english_prose_bpe_mod20480"
model_family = "deepseek_v3"
vocab_size = 20480
n_layer = 6
num_dense_layers = 1
n_embd = 2048
n_head = 16
kv_lora_rank = 512
qk_nope_head_dim = 128
qk_rope_head_dim = 64
v_head_dim = 128
q_lora_rank = 0
rope_theta = 50000.0
rms_norm_eps = 1e-5
intermediate_size = 11264
moe_intermediate_size = 1408
n_shared_experts = 2
num_experts = 64
num_experts_per_tok = 6
experts_held = (0, 8)
n_group = 1
topk_group = 1
route_scale = 2.446
route_norm = True
block_size = 8192
batch_size = 2
gradient_accumulation_steps = 1
remat = True
remat_policy = "save_attention"
loss_chunk_size = 1024
max_iters = 3000
lr_decay_iters = 3000
warmup_iters = 2000
eval_interval = 500
eval_iters = 10
log_interval = 20
learning_rate = 3e-4
min_lr = 3e-5
compute_dtype = "bfloat16"
attention_impl = "auto"
