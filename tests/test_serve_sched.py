"""Integration: the serving launcher's continuous-batching step loop."""
import pytest

from repro.launch import serve


def test_serve_scheduler_loop_end_to_end(capsys):
    serve.main([
        "--arch", "rwkv6-3b", "--smoke", "--requests", "6", "--gen-len", "4",
        "--prompt-len", "8", "--decode-batch", "2", "--fleet", "2",
        "--policy", "energy-fair",
    ])
    out = capsys.readouterr().out
    assert "served 6/6 requests" in out
    assert "energy-fair intervals" in out
    assert "per-request energy SLO accounting" in out
    # every request row is printed with measured energy attributed
    for rid in range(6):
        assert f"\n  {rid:>3} client" in out


def test_serve_budget_rejects_when_exhausted(capsys):
    serve.main([
        "--arch", "rwkv6-3b", "--smoke", "--requests", "4", "--gen-len", "4",
        "--prompt-len", "8", "--decode-batch", "2", "--fleet", "0",
        "--budget-j", "1e-12",  # nothing fits
    ])
    out = capsys.readouterr().out
    assert "served 0/4 requests" in out
    assert "(4 rejected by SLO)" in out


def test_serve_bills_only_real_tokens(capsys):
    # 3 requests on 2 slots: the last interval decodes with one padded slot,
    # so billed tokens < decoded tokens and only real tokens are reported
    serve.main([
        "--arch", "rwkv6-3b", "--smoke", "--requests", "3", "--gen-len", "4",
        "--prompt-len", "8", "--decode-batch", "2", "--fleet", "0",
    ])
    out = capsys.readouterr().out
    assert "served 3/3 requests" in out
    assert "(0 rejected by SLO), 12 tokens" in out  # 3 x 4, padding excluded
    assert "slot utilization:" in out
    assert "padded slots excluded" in out


def test_serve_churn_arrivals_mid_decode(capsys):
    # requests trickle in every 2 decode steps, joining the live batch
    # mid-decode; all finish and all their tokens are billed exactly once
    serve.main([
        "--arch", "rwkv6-3b", "--smoke", "--requests", "5", "--gen-len", "6",
        "--prompt-len", "8", "--decode-batch", "2", "--fleet", "2",
        "--arrive-every", "2", "--steps-per-sync", "3",
    ])
    out = capsys.readouterr().out
    assert "served 5/5 requests" in out
    assert "(0 rejected by SLO), 30 tokens" in out  # 5 x 6, billed exactly once
    for rid in range(5):
        assert f"\n  {rid:>3} client" in out


def test_serve_paged_kv_backend_end_to_end(capsys):
    # paged cache backend on an attention arch: admission allocates pages,
    # retire frees them — every page is back in the pool at exit, and churn
    # over more requests than slots actually reuses freed pages
    res = serve.main([
        "--arch", "qwen25-3b", "--smoke", "--kv", "paged", "--page-size", "8",
        "--requests", "5", "--gen-len", "4", "--prompt-len", "8",
        "--decode-batch", "2", "--fleet", "2", "--arrive-every", "2",
    ])
    assert res["served"] == 5 and res["rejected"] == 0
    assert res["billed_tokens"] == 20 and res["decode_steps"] >= 4
    out = capsys.readouterr().out
    assert "served 5/5 requests" in out
    assert "(0 rejected by SLO), 20 tokens" in out  # 5 x 4, billed exactly once
    assert "paged KV: page size 8" in out
    assert "0 in use at exit" in out  # retire freed every reservation
    import re

    m = re.search(r"\((\d+) reused", out)
    assert m and int(m.group(1)) > 0, "churn over 2 slots must reuse freed pages"


def test_serve_paged_kv_matches_dense_backend(capsys):
    # same workload, both backends: the billing/throughput accounting and
    # the served set must agree (the decode math is pinned equivalent in
    # test_paged_attention.py)
    args = ["--arch", "qwen25-3b", "--smoke", "--requests", "3", "--gen-len",
            "4", "--prompt-len", "8", "--decode-batch", "2", "--fleet", "0"]
    serve.main(args + ["--kv", "dense"])
    dense_out = capsys.readouterr().out
    serve.main(args + ["--kv", "paged", "--page-size", "8"])
    paged_out = capsys.readouterr().out
    assert "served 3/3 requests" in dense_out
    assert "served 3/3 requests" in paged_out
    assert "(0 rejected by SLO), 12 tokens" in paged_out


def test_serve_paged_kv_rejected_without_attention():
    # rwkv6 has no attention layers: the paged backend must refuse to start
    with pytest.raises(SystemExit):
        serve.main(["--arch", "rwkv6-3b", "--smoke", "--kv", "paged"])
