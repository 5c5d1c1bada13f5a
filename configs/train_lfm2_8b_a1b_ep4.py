# LiquidAI LFM2-8B-A1B's block (HF model_type lfm2_moe) as ONE CHIP'S SHARE of
# a 4-way expert-parallel pretraining job, on one v5e chip (16 GB):
#
#   python -m nanosandbox_tpu.data.prepare english_prose_bpe --fold_vocab=16384
#   python -m nanosandbox_tpu.train configs/train_lfm2_8b_a1b_ep4.py
#
# Published widths (huggingface.co/LiquidAI/LFM2-8B-A1B config.json): hidden
# 2048, gated short convolutions of 3 taps beside full causal attention with
# 32 query heads on 8 KV heads of 64 (rotary theta 1e6), dense width 7168, 32
# routed experts of width 1792, 4 a token, sigmoid scores normalised, no
# shared expert, tied head. Cut to the chip
# (chipbench/configs/lfm2-8b-a1b-ep4.json says why, key by key): the first
# six layers of the published pattern (one dense), experts 0..7 of every
# expert layer (rank 0 of 4; the router still scores all 32 and what the
# absent experts would add is left out), rows 0..16383 of the vocabulary
# (ids folded into the slice by the preparer). 612.8 M parameters, 9.8 GB
# of parameters, gradients and Adam state; remat for the rest.
out_dir = "runs/lfm2_8b_a1b_ep4"
dataset = "english_prose_bpe_mod16384"
model_family = "lfm2"
vocab_size = 16384
n_layer = 6
layer_types = "conv,conv,full,conv,conv,conv"
conv_L_cache = 3
num_dense_layers = 1
n_embd = 2048
n_head = 32
n_kv_head = 8
head_dim = 64
rope_theta = 1000000.0
rms_norm_eps = 1e-5
intermediate_size = 7168
moe_intermediate_size = 1792
num_experts = 32
num_experts_per_tok = 4
experts_held = (0, 8)
route_scale = 1.0
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
