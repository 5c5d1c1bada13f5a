"""Multi-token decode scan (ISSUE 12), the lag-k contract's greedy token
PARITY: a scan_k in {2, 4, 8} engine emits exactly the scan_k=1 engine's
tokens across paged/dense pools and fp32/int8/int4 KV modes — chunks are
dispatch boundaries, not sampling state. The rest of the contract is
tests/test_scan_decode.py's."""

import pytest

from _scan_common import _mixed_reqs, _run, served_model  # noqa: F401


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
def test_scan_greedy_parity_all_modes(served_model, paged, kv_dtype):
    """scan_k in {2, 4, 8} vs single-step: token-identical outputs on a
    mixed continuous-batching workload, per pool layout and KV mode."""
    _, model, params = served_model
    reqs = _mixed_reqs(seed=3)
    _, base = _run(model, params, reqs, paged=paged, kv_dtype=kv_dtype)
    for k in (2, 4, 8):
        _, out = _run(model, params, reqs, paged=paged,
                      kv_dtype=kv_dtype, scan_k=k)
        assert out == base, f"scan_k={k} diverged"
