# ByteDance's Ouro-2.6B LoopLM (HF model_type ouro) as ONE STAGE of an 8-stage
# pipeline, on one v5e chip (16 GB):
#
#   python -m nanosandbox_tpu.data.prepare english_prose_bpe --fold_vocab=49152
#   python -m nanosandbox_tpu.train configs/train_ouro_2_6b_pp8.py
#
# Published widths (huggingface.co/ByteDance/Ouro-2.6B config.json): hidden
# 2048, 16 heads of 128 (16 KV heads), SwiGLU 5632, rotary theta 1e6,
# RMSNorm eps 1e-6 (four a layer), vocabulary 49,152 with an untied head, and
# total_ut_steps 4: the stack of layers runs four times a step, an exit (head
# + exit gate) after every pass, the loss over the four exits weighted by the
# gate. Cut to the chip (chipbench/configs/ouro-2.6b-pp8.json says why): 6 of
# the 48 layers (stage 0 of 8, which also holds the final norm, the head and
# the gate), each run in all four passes. 509.7 M parameters, 8.2 GB of
# parameters, gradients and Adam state; remat for the rest.
out_dir = "runs/ouro_2_6b_pp8"
dataset = "english_prose_bpe_mod49152"
model_family = "ouro"
vocab_size = 49152
n_layer = 6
n_embd = 2048
n_head = 16
n_kv_head = 16
head_dim = 128
intermediate_size = 5632
total_ut_steps = 4
rope_theta = 1000000.0
rms_norm_eps = 1e-6
block_size = 8192
batch_size = 2
gradient_accumulation_steps = 1
remat = True
remat_policy = "full"
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
