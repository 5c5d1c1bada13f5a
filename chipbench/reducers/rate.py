"""The whole step's share of the chip's peak."""


def train_step_mfu_pct(run: dict, metric: dict):
    """Window tokens/s x the operations a token requires (chipbench/flops.py:
    6 N + causal attention; rematerialised work not counted) over chips x
    the published bf16 peak."""
    rec = run["record"]
    if not rec.get("tokens") or run["peaks"] is None:  # no chip with known peaks
        return None
    achieved = rec["tokens"] / rec["window_s"] * rec["flops_per_token"]
    peak = rec["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * achieved / peak
