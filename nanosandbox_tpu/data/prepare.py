"""Dataset preparation: corpus -> train.bin / val.bin / meta.pkl.

Reimplements the contract of nanoGPT's ``data/<dataset>/prepare.py`` as the
reference exercises it (ipynb:50-56; k8s dataset Job, README.md:48-53,
gh_sync.ps1:124-128): download/read a corpus, tokenize, write uint16 memmap
bins with a 90/10 train/val split and a meta.pkl describing the vocab.

Network access goes through the cluster proxy when configured (the proxy
ConfigMap's env is honored automatically by urllib). When the network is
unavailable, a local source file can be supplied, or — for smoke tests — a
deterministic synthetic corpus is generated (the reference's scale-down
testing philosophy, SURVEY.md §4).
"""

from __future__ import annotations

import os
import pickle
import urllib.request

import numpy as np

from nanosandbox_tpu.data.tokenizer import ByteTokenizer, CharTokenizer, get_tokenizer

TINY_SHAKESPEARE_URL = (
    "https://raw.githubusercontent.com/karpathy/char-rnn/master/data/"
    "tinyshakespeare/input.txt"
)


def _warn_synthetic(what: str) -> None:
    import sys

    print(f"WARNING: {what} unavailable — using a SYNTHETIC corpus. "
          "This is only valid for smoke tests; do not train real models "
          "on it. Pass allow_synthetic=False to fail instead.",
          file=sys.stderr)


def _synthetic_corpus(n_chars: int = 200_000, seed: int = 1337) -> str:
    """Deterministic pseudo-text for offline smoke tests (Tier-0, SURVEY §4)."""
    rng = np.random.default_rng(seed)
    words = ["the", "and", "lord", "king", "thou", "hath", "speak", "good",
             "night", "come", "what", "shall", "more", "love", "death",
             "crown", "sword", "blood", "heart", "light"]
    parts: list[str] = []
    total = 0
    while total < n_chars:
        n = int(rng.integers(4, 12))
        sent = " ".join(words[int(i)] for i in rng.integers(0, len(words), n))
        sent = sent.capitalize() + ".\n"
        parts.append(sent)
        total += len(sent)
    return "".join(parts)[:n_chars]


def fetch_corpus(out_path: str, url: str = TINY_SHAKESPEARE_URL,
                 source_file: str | None = None,
                 allow_synthetic: bool = True) -> str:
    """Obtain the raw corpus text: local file > cached copy > download > synthetic."""
    if source_file and os.path.exists(source_file):
        with open(source_file, "r", encoding="utf-8") as f:
            return f.read()
    if os.path.exists(out_path):
        with open(out_path, "r", encoding="utf-8") as f:
            return f.read()
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            text = r.read().decode("utf-8")
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as f:
            f.write(text)
        return text
    except Exception:
        if not allow_synthetic:
            raise
        _warn_synthetic(f"download of {url}")
        return _synthetic_corpus()


def write_bins(ids: np.ndarray, out_dir: str, meta: dict,
               val_fraction: float = 0.1) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    n = len(ids)
    split = int(n * (1 - val_fraction))
    train_ids = ids[:split].astype(np.uint16)
    val_ids = ids[split:].astype(np.uint16)
    train_ids.tofile(os.path.join(out_dir, "train.bin"))
    val_ids.tofile(os.path.join(out_dir, "val.bin"))
    with open(os.path.join(out_dir, "meta.pkl"), "wb") as f:
        pickle.dump(meta, f)
    return {"train_tokens": len(train_ids), "val_tokens": len(val_ids),
            "vocab_size": meta["vocab_size"]}


def folded_name(dataset: str, vocab: int) -> str:
    return f"{dataset}_mod{vocab}"


def fold_vocab(src_dir: str, out_dir: str, vocab: int) -> dict:
    """A prepared set with every id folded into [0, vocab) (``id mod
    vocab``), same splits: the corpus for a model that holds a SLICE of a
    vocabulary (one rank of a vocabulary-parallel job: its embedding and
    head have ``vocab`` rows, and logits and loss are over the slice)."""
    os.makedirs(out_dir, exist_ok=True)
    stats = {"vocab_size": vocab}
    for split in ("train", "val"):
        ids = np.fromfile(os.path.join(src_dir, f"{split}.bin"),
                          dtype=np.uint16)
        (ids % vocab).astype(np.uint16).tofile(
            os.path.join(out_dir, f"{split}.bin"))
        stats[f"{split}_tokens"] = len(ids)
    with open(os.path.join(out_dir, "meta.pkl"), "wb") as f:
        pickle.dump({"vocab_size": vocab, "kind": "folded",
                     "source": os.path.basename(src_dir)}, f)
    return stats


def prepare_char_dataset(out_dir: str, source_file: str | None = None,
                         url: str = TINY_SHAKESPEARE_URL,
                         allow_synthetic: bool = True) -> dict:
    """tiny-shakespeare char-level prep (ipynb:52-56 contract)."""
    text = fetch_corpus(os.path.join(out_dir, "input.txt"), url=url,
                        source_file=source_file,
                        allow_synthetic=allow_synthetic)
    tok = CharTokenizer.from_text(text)
    ids = np.asarray(tok.encode(text), dtype=np.uint16)
    return write_bins(ids, out_dir, tok.meta())


# Resolved relative to the repo checkout (shared with tokenizer.py), not
# the CWD, so the fixture preps work from any working directory — e.g.
# the k8s dataset Job runs with the PVC as CWD.
from nanosandbox_tpu.data.tokenizer import _REPO_ROOT  # noqa: E402

REAL_FIXTURE = os.path.join(_REPO_ROOT, "data", "fixtures",
                            "english_prose.txt")


def _prepare_fixture_dataset(out_dir: str, fixture: str, build_hint: str,
                             make_tokenizer, source_file: str | None) -> dict:
    """Shared prep for the committed real-text fixtures: resolve the
    source (explicit file > fixture), fail loudly with the build command
    when absent (no synthetic fallback — real data or a loud failure),
    tokenize, write bins."""
    src = source_file or fixture
    if not os.path.exists(src):
        raise FileNotFoundError(
            f"{src} not found — run `{build_hint}` (or pass --source_file)")
    with open(src, "r", encoding="utf-8") as f:
        text = f.read()
    tok = make_tokenizer(text)
    ids = np.asarray(tok.encode(text), dtype=np.uint16)
    return write_bins(ids, out_dir, tok.meta())


def prepare_english_prose_dataset(out_dir: str,
                                  source_file: str | None = None) -> dict:
    """Char-level prep of the committed REAL-text fixture.

    The zero-egress counterpart of the tiny-shakespeare flow
    (the reference notebook downloads its corpus over the network;
    this environment cannot): ``scripts/make_real_corpus.py`` assembles
    ~4 MB of human-written English from redistributable in-image prose
    and commits it at data/fixtures/english_prose.txt.
    """
    return _prepare_fixture_dataset(
        out_dir, REAL_FIXTURE, "python scripts/make_real_corpus.py",
        CharTokenizer.from_text, source_file)


XL_FIXTURE = os.path.join(_REPO_ROOT, "data", "fixtures",
                          "english_prose_xl.txt")


def prepare_english_prose_bpe_dataset(out_dir: str,
                                      source_file: str | None = None) -> dict:
    """GPT-2-regime prep of the committed XL real-text fixture with the
    committed 50,257-entry byte-BPE vocab (scripts/make_bpe_vocab.py) —
    the zero-egress counterpart of the reference's tiktoken/OpenWebText
    flow (ipynb:37, gh_sync.ps1:144-148). Real text, real BPE tokens, no
    network, no synthetic fallback."""
    return _prepare_fixture_dataset(
        out_dir, XL_FIXTURE,
        "python scripts/make_real_corpus.py --out "
        "data/fixtures/english_prose_xl.txt --max_mb 100 --profile xl",
        lambda _text: get_tokenizer("bpe"), source_file)


def download_openwebtext(num_chars: int, dataset_name: str = "Skylion007/openwebtext"
                         ) -> str:
    """Stream an OpenWebText subset via HF datasets (backlog #22's "small
    OWT subset ... size via env"). Raises if the `datasets` package or the
    network is unavailable — callers decide whether synthetic is acceptable.
    """
    import datasets  # noqa: PLC0415 — optional dep, only needed for OWT

    stream = datasets.load_dataset(dataset_name, split="train", streaming=True)
    chunks: list[str] = []
    total = 0
    for ex in stream:
        doc = ex.get("text", "")
        chunks.append(doc)
        total += len(doc) + 1
        if total >= num_chars:
            break
    return "\n".join(chunks)[:num_chars]


def prepare_bpe_dataset(out_dir: str, source_files: list[str] | None = None,
                        text: str | None = None, tokenizer: str = "gpt2",
                        num_chars: int | None = None,
                        allow_synthetic: bool = True,
                        download: bool = True,
                        allow_byte_fallback: bool = False) -> dict:
    """OpenWebText-style prep (backlog item #22, gh_sync.ps1:144-148).

    Source resolution order: explicit ``text`` > ``source_files`` > streamed
    OpenWebText download (capped at ``num_chars``) > synthetic (only when
    ``allow_synthetic``, with a loud warning). Tokenizes with the requested
    tokenizer ('gpt2' tiktoken, 'bpe' committed offline vocab, 'byte').

    A tokenizer that can't construct (e.g. 'gpt2' offline) FAILS by
    default: silently producing vocab-256 byte bins for a dataset the
    training config budgets 50k vocab for invalidates the run. Pass
    ``allow_byte_fallback=True`` (CLI: --allow_byte_fallback) to opt into
    the downgrade, which is then recorded loudly and in meta.pkl.
    """
    if text is None:
        chunks = []
        for p in source_files or []:
            with open(p, "r", encoding="utf-8") as f:
                chunks.append(f.read())
        text = "\n".join(chunks)
    if not text and download:
        try:
            text = download_openwebtext(num_chars or 10_000_000)
        except Exception:
            if not allow_synthetic:
                raise
    if not text:
        if not allow_synthetic:
            raise ValueError("no source text provided and download failed")
        _warn_synthetic("openwebtext download")
        text = _synthetic_corpus(n_chars=num_chars or 1_000_000)
    if num_chars:
        text = text[:num_chars]
    try:
        tok = get_tokenizer(tokenizer)
    except (RuntimeError, FileNotFoundError, ImportError) as e:
        if not allow_byte_fallback:
            raise RuntimeError(
                f"tokenizer {tokenizer!r} unavailable and byte fallback is "
                "opt-in (pass allow_byte_fallback=True / "
                "--allow_byte_fallback to accept vocab-256 bins)") from e
        import sys

        print(f"WARNING: tokenizer {tokenizer!r} unavailable — downgrading "
              "to the vocab-256 BYTE tokenizer (allow_byte_fallback=True). "
              f"Cause: {e}", file=sys.stderr)
        tok = ByteTokenizer()
    ids = np.asarray(tok.encode(text), dtype=np.uint16)
    return write_bins(ids, out_dir, tok.meta())


def main(argv: list[str] | None = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="prepare dataset bins")
    ap.add_argument("dataset", choices=["shakespeare_char", "openwebtext",
                                        "english_prose_char",
                                        "english_prose_bpe"])
    ap.add_argument("--data_dir", default=os.environ.get("DATA_DIR", "data"))
    ap.add_argument("--source_file", default=None)
    ap.add_argument("--num_chars", type=int,
                    default=int(os.environ.get("DATASET_NUM_CHARS", "0")) or None)
    ap.add_argument("--tokenizer", default="gpt2")
    ap.add_argument("--fold_vocab", type=int, default=0,
                    help="also write <dataset>_mod<N>: the same tokens with "
                         "every id folded into [0, N) (fold_vocab)")
    # shakespeare_char is the smoke-test dataset: synthetic fallback stays on
    # by default (reference scale-down philosophy). openwebtext is a REAL
    # training corpus: silent synthetic data would invalidate runs, so it
    # fails loudly unless explicitly allowed (env for the k8s Job).
    # BooleanOptionalAction so BOTH directions are expressible on the CLI
    # (--allow_synthetic / --no-allow_synthetic); None falls through to the
    # DATASET_ALLOW_SYNTHETIC env var, then the per-dataset default.
    ap.add_argument("--allow_synthetic", default=None,
                    action=argparse.BooleanOptionalAction)
    ap.add_argument("--allow_byte_fallback", action="store_true",
                    help="accept a vocab-256 byte downgrade when the "
                         "requested BPE tokenizer is unavailable (off by "
                         "default: a silent downgrade invalidates runs "
                         "configured for a 50k vocab)")
    args = ap.parse_args(argv)
    allow_synth = args.allow_synthetic
    if allow_synth is None:
        env = os.environ.get("DATASET_ALLOW_SYNTHETIC", "")
        allow_synth = (env == "1") if env else (args.dataset == "shakespeare_char")

    out_dir = os.path.join(args.data_dir, args.dataset)
    if args.dataset == "english_prose_char":
        stats = prepare_english_prose_dataset(out_dir,
                                              source_file=args.source_file)
    elif args.dataset == "english_prose_bpe":
        stats = prepare_english_prose_bpe_dataset(
            out_dir, source_file=args.source_file)
    elif args.dataset == "shakespeare_char":
        stats = prepare_char_dataset(out_dir, source_file=args.source_file,
                                     allow_synthetic=allow_synth)
    else:
        stats = prepare_bpe_dataset(
            out_dir, source_files=[args.source_file] if args.source_file else None,
            tokenizer=args.tokenizer, num_chars=args.num_chars,
            allow_synthetic=allow_synth,
            allow_byte_fallback=args.allow_byte_fallback)
    print(f"prepared {args.dataset} -> {out_dir}: {stats}")
    if args.fold_vocab:
        folded = os.path.join(args.data_dir,
                              folded_name(args.dataset, args.fold_vocab))
        print(f"folded -> {folded}: "
              f"{fold_vocab(out_dir, folded, args.fold_vocab)}")


if __name__ == "__main__":
    main()
