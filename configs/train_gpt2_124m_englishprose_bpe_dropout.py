# The REGULARIZED GPT-2-regime run: identical to
# train_gpt2_124m_englishprose_bpe.py except dropout 0.1, which runs
# INSIDE the Pallas flash kernels (r4: flash_attention's dropout — the r3
# convergence runs fell to the ~10%-MFU XLA fallback whenever dropout was
# on). Two things this artifact demonstrates at once:
#   1. the in-kernel dropout path sustaining a real 124M training run on
#      real BPE tokens at flash-kernel speed (BASELINE.md A/B: 82.4k
#      tok/s vs 42.7k on the XLA fallback at this exact shape);
#   2. regularization vs the dropout-0 twin on the same 5.46M-token
#      corpus, where the unregularized run's val curve knees into
#      memorization at ~9 epochs (best val 3.052 @ 2500).
out_dir = "runs_r4/gpt2_124m_englishprose_bpe_dropout"
# Hardware RNG for the dropout mask stream: threefry mask generation is
# ~half the e2e cost of dropout>0 configs on TPU (A/B in BASELINE.md —
# 93.5k vs 85.7k tok/s at this exact shape); same statistics, different
# bits, so only the mask realization changes.
rng_impl = "rbg"
dataset = "english_prose_bpe"
vocab_size = 50304  # dataset meta says 50257; padded to 64 for the MXU
n_layer = 12
n_head = 12
n_embd = 768
block_size = 1024
batch_size = 16
gradient_accumulation_steps = 1
dropout = 0.1
max_iters = 3000
lr_decay_iters = 3000
warmup_iters = 100
eval_interval = 250
eval_iters = 20
log_interval = 50
learning_rate = 6e-4
min_lr = 6e-5
compute_dtype = "bfloat16"
attention_impl = "auto"
