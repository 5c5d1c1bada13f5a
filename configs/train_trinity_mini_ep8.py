# Arcee Trinity-Mini's block (HF model_type afmoe) as ONE CHIP'S SHARE of an
# 8-way expert-parallel pretraining job, on one v5e chip (16 GB):
#
#   python -m nanosandbox_tpu.data.prepare english_prose_bpe --fold_vocab=25024
#   python -m nanosandbox_tpu.train configs/train_trinity_mini_ep8.py
#
# Published widths (huggingface.co/arcee-ai/Trinity-Mini config.json): hidden
# 2048, 32 query heads on 4 KV heads of 128, window 2048 on sliding layers,
# dense width 6144, 128 routed experts of width 1024, 8 a token, one shared
# expert, sigmoid scores normalised and scaled by 2.826. Cut to the chip
# (chipbench/configs/trinity-mini-ep8.json says why, key by key): the first
# five layers of the published pattern (one dense), experts 0..15 of every
# expert layer (rank 0 of 8; the router still scores all 128 and what the
# absent experts would add is left out), rows 0..25023 of the vocabulary
# (ids folded into the slice by the preparer). 705.5 M parameters, 11.3 GB
# of parameters, gradients and Adam state; remat for the rest.
out_dir = "runs/trinity_mini_ep8"
dataset = "english_prose_bpe_mod25024"
model_family = "afmoe"
vocab_size = 25024
n_layer = 5
layer_types = "sliding,sliding,sliding,full,sliding"
num_dense_layers = 1
n_embd = 2048
n_head = 32
n_kv_head = 4
head_dim = 128
sliding_window = 2048
rope_theta = 10000.0
rms_norm_eps = 1e-5
intermediate_size = 6144
moe_intermediate_size = 1024
num_experts = 128
num_experts_per_tok = 8
experts_held = (0, 16)
route_scale = 2.826
route_norm = True
mup_enabled = True
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
