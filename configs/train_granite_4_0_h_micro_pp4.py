# IBM's Granite 4.0-H Micro (HF model_type granitemoehybrid, no experts) as ONE
# STAGE of a 4-stage pipeline, on one v5e chip (16 GB):
#
#   python -m nanosandbox_tpu.data.prepare english_prose_bpe --fold_vocab=25088
#   python -m nanosandbox_tpu.train configs/train_granite_4_0_h_micro_pp4.py
#
# Published widths (huggingface.co/ibm-granite/granite-4.0-h-micro
# config.json): hidden 2048; Mamba-2 layers of 64 heads x 64 (d_inner 4096),
# d_state 128, one group, a 4-tap conv with a bias, the scan in chunks of 256;
# one grouped-query attention layer in ten (32 query heads, 8 KV heads of 64,
# no positions); a shared SwiGLU of 8192 in every layer; the muP multipliers
# (embedding 12, residual 0.22, attention 1/64, logits / 8); RMSNorm eps
# 1e-5; a tied table. Cut to the chip (chipbench/configs/
# granite-4.0-h-micro-pp4.json says why): the first ten layers (one period:
# five Mamba, attention, four Mamba; stage 0 of 4) and a quarter of the
# vocabulary. 797.9 M parameters, 12.8 GB of parameters, gradients and Adam
# state; remat for the rest.
out_dir = "runs/granite_4_0_h_micro_pp4"
dataset = "english_prose_bpe_mod25088"
model_family = "granite"
vocab_size = 25088
n_layer = 10
layer_types = ("mamba,mamba,mamba,mamba,mamba,attention,"
               "mamba,mamba,mamba,mamba")
n_embd = 2048
n_head = 32
n_kv_head = 8
head_dim = 64
intermediate_size = 8192
mamba_n_heads = 64
mamba_d_head = 64
mamba_d_state = 128
mamba_n_groups = 1
mamba_d_conv = 4
mamba_chunk_size = 256
embedding_multiplier = 12.0
residual_multiplier = 0.22
attention_multiplier = 0.015625
logits_scaling = 8.0
rms_norm_eps = 1e-5
block_size = 8192
batch_size = 1
gradient_accumulation_steps = 1
remat = True
remat_policy = "save_attention"
loss_chunk_size = 1024
max_iters = 4000
lr_decay_iters = 4000
warmup_iters = 200
eval_interval = 500
eval_iters = 10
log_interval = 20
learning_rate = 3e-4
min_lr = 3e-5
compute_dtype = "bfloat16"
attention_impl = "auto"
