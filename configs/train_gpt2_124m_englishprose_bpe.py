# GPT-2-REGIME convergence evidence on real tokens (round-3 VERDICT
# "Next round" #1): GPT-2 124M (12L/12H/768d, block 1024, vocab 50304)
# trained on the committed XL real-English corpus tokenized with the
# committed 50,257-entry byte-BPE vocab (scripts/make_bpe_vocab.py) —
# the first run in the evidence chain where the LM head, chunked loss,
# and embedding paths see real tokens at the vocabulary scale they were
# sized for (the reference's tiktoken/OpenWebText contract, ipynb:37).
#
# Scale note: 5.46M train tokens under 16x1024 batches is ~333
# iters/epoch; 3000 iters is ~9 epochs, so the recorded val curve shows
# real-language learning first and the memorization knee after — both
# are the point of the artifact.
out_dir = "runs_r4/gpt2_124m_englishprose_bpe"
dataset = "english_prose_bpe"
vocab_size = 50304  # dataset meta says 50257; padded to 64 for the MXU
n_layer = 12
n_head = 12
n_embd = 768
block_size = 1024
batch_size = 16
gradient_accumulation_steps = 1
dropout = 0.0
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
# loss_chunk_size stays on the -1 auto default: at 16x1024x50304 the f32
# logits fit the 4 GB budget, so it resolves to 0 (full logits) — the
# measured-faster path. A July sweep of this config's unpinned surface is
# kept as a record (benchmarks/r4/sweep_autoconfig.json).
profile_steps = "1000:1003"
