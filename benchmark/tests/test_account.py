"""``readers/account.py`` and ``readers/gpt.py``: device time by innermost
named scope, the recompute marker, the idle gaps cut by program span.

``data/phase_probe.xplane.pb`` is a trace of one TPU v5e, recorded by

    chiprun -- python3 benchmark/tests/phase_probe.py

(a 2-layer GPT of 2 heads of 128 behind ``InferenceEngine`` on 4 slots,
five ticks, one admission among them, the caller pausing 2 ms after the
fourth) and cut down to the first chip's plane and the engine's thread by
``phase_probe.py slim``.  The other planes here are synthetic.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.readers import account, gpt, nemotron  # noqa: E402

PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "phase_probe.xplane.pb")
STEP = "jit(step)/fwd_bwd/"
BODY = "while/body/closed_call/"


def test_the_innermost_scope_wins_and_words_are_whole():
    leaf = account.leaf_of
    assert leaf(STEP + "jvp()/" + BODY + "attn/attn_core/dot_general:") \
        == "attn_core"
    assert leaf(STEP + "jvp()/" + BODY + "attn/add:") == "attn"
    assert leaf(STEP + "jvp()/while/body/dynamic_update_slice:") == "fwd_bwd"
    assert leaf("jit(step)/optimizer/mul:") == "optimizer"
    assert leaf(STEP + "transpose(jvp(moe))/expert_ffn/x:") == "expert_ffn"
    assert leaf(STEP + "jvp(kda)/kda_conv/custom_vjp_call/mul:") \
        == "kda_conv"
    assert leaf("jit(_decode_fn)/attn/decode_attn/pallas_call:") \
        == "decode_attn"
    # a parameter's name holds a word of the vocabulary and is no scope
    assert leaf("params['gpt.blocks.0.mlp.up_proj.weight']") is None
    assert leaf("jit(_decode_fn)/mlp_like/attn2/add:") is None
    assert leaf("") is None


def test_the_recompute_marker_and_the_backward_are_read_from_the_path():
    fwd = STEP + "jvp()/" + BODY + "attn/attn_core/k"
    remat = STEP + "transpose(jvp())/" + BODY + \
        "checkpoint/rematted_computation/attn/attn_core/k"
    bwd = STEP + "transpose(jvp())/" + BODY + "checkpoint/attn/attn_core/k"
    assert [account.pass_of(p) for p in (fwd, remat, bwd)] == \
        ["fwd", "remat", "bwd"]
    ops = [(fwd, 0, 100), (remat, 100, 70), (bwd, 200, 250),
           (STEP + "jvp()/while", 500, 100),
           (STEP + "jvp()/" + BODY + "mlp/dot", 510, 60),
           ("copy.1", 700, 5)]
    table = account.account(ops)
    assert table["attn_core"] == {"fwd": 100, "remat": 70, "bwd": 250}
    assert table["mlp"] == {"fwd": 60, "remat": 0, "bwd": 0}
    # the while's own time, not its span, is the trainer's glue
    assert table["fwd_bwd"] == {"fwd": 40, "remat": 0, "bwd": 0}
    assert table[account.UNSCOPED] == {"fwd": 5, "remat": 0, "bwd": 0}
    # the rows tile the traced time
    assert sum(sum(r.values()) for r in table.values()) == 525


def test_a_block_scope_round_an_old_scope_leaves_its_row_unchanged():
    """``scope_device_ms`` (``own_time_by_scope``) finds a name anywhere
    in a path, so wrapping ``expert_ffn`` in the block scope ``moe`` moves
    nothing; the account gives the operation to the inner scope."""
    bare = [("jit(step)/transpose(jvp(expert_ffn))/dot:", 0, 70),
            ("jit(step)/jvp(moe_route)/top_k:", 80, 10)]
    nested = [("jit(step)/fwd_bwd/transpose(jvp(moe))/expert_ffn/dot:", 0, 70),
              ("jit(step)/fwd_bwd/jvp(moe)/moe_route/top_k:", 80, 10),
              ("jit(step)/fwd_bwd/jvp(moe)/add:", 95, 5)]
    scopes = ["expert_ffn", "moe_route"]
    assert nemotron.own_time_by_scope(bare, scopes) == \
        nemotron.own_time_by_scope(nested, scopes) == \
        {"expert_ffn": 70, "moe_route": 10}
    table = account.account(nested)
    assert table["expert_ffn"]["bwd"] == 70 and table["moe"]["fwd"] == 5


def spans_of(*rows):
    return sorted(((s, e, n, {}) for s, e, n in rows),
                  key=lambda r: (r[0], -r[1]))


def test_gaps_are_cut_exactly_at_the_spans_edges():
    #  busy:   [0, 100]            [160, 300]      [340, 400]
    #  spans:  tick [90, 330) = read [95, 130) commit [130, 150) then
    #          nothing of the program until tick [335, 500) = admit [335,
    #          338) launch [338, 345)
    busy = [(0, 100), (160, 300), (340, 400)]
    spans = spans_of((90, 330, "tick"), (95, 130, "tick/read"),
                     (130, 150, "tick/commit"), (335, 500, "tick"),
                     (335, 338, "tick/admit"), (338, 345, "tick/launch"))
    cut = account.cut_gaps(busy, spans)
    # gap [100, 160): read 30, commit 20, then the tick itself 10
    # gap [300, 340): tick 30, outside 5, admit 3, launch 2
    assert cut == {"read": 30.0, "host": 20.0 + 10 + 30 + 3,
                   "launch": 2.0, "outside": 5.0}
    assert sum(cut.values()) == (160 - 100) + (340 - 300)
    # no span at all: the whole idle time is the caller's
    assert account.cut_gaps(busy, [])["outside"] == 100.0


def test_the_innermost_span_at_each_instant():
    spans = spans_of((0, 100, "tick"), (10, 60, "tick/admit"),
                     (20, 50, "prefill"), (60, 90, "tick/launch"),
                     (120, 130, "train_step/read"))
    assert account.innermost_segments(spans) == [
        (0, 10, "tick"), (10, 20, "tick/admit"), (20, 50, "prefill"),
        (50, 60, "tick/admit"), (60, 90, "tick/launch"), (90, 100, "tick"),
        (120, 130, "train_step/read")]


def test_the_costs_from_the_shapes_alone():
    m = harness.load_json(harness.HERE, "configs",
                          "gpt3-350m-train.json")["model"]["kwargs"]
    cost = gpt.attn_core_cost(m, 6, 2048)
    # 24 layers x 6 rows x 16 heads: half the square of 2048 at 64 wide,
    # two products, 2 operations a multiply-add, times 3.5
    assert cost["flops"] == pytest.approx(
        3.5 * 24 * 6 * 16 * 2 * 2048 * 2048 * 64)
    assert cost["flops"] / 197e12 == pytest.approx(21.98e-3, rel=0.01)
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
    s = harness.load_json(harness.HERE, "configs",
                          "gpt3-1.3b-serve.json")["model"]["kwargs"]
    # 24 full slots of 2048 positions: one read of the whole 9.0 GiB cache
    whole = gpt.decode_attn_cost(s, 24 * 2048)
    assert whole["bytes"] == 24 * 2048 * 24 * 2 * 16 * 128 * 2 == 9 * 2 ** 30
    assert whole["bytes"] / 819e9 > whole["flops"] / 197e12


def test_readers_return_nothing_where_there_is_nothing(monkeypatch):
    assert account.leaf_device_ms({"trace": None}, {"scope": "mlp"}) is None
    assert account.unscoped_pct({"trace": None}, {}) is None
    assert account.gap_ms({"trace": None}, {"phase": "read"}) is None
    monkeypatch.setattr(account, "newest_trace", lambda: None)
    obs = {"trace": {"busy_s": 1}, "kind": "serve"}
    assert account.leaf_device_ms(obs, {"scope": "decode_attn"}) is None
    assert gpt.decode_attn_roofline_pct(
        obs, {"config": "gpt3-1.3b-serve"}) is None


def test_a_trace_without_scopes_or_spans_reads_nothing(monkeypatch):
    """PR 33's probe has neither a ``tick`` nor this PR's scopes: the
    serving readers find no tick to divide by, the training readers no
    such leaf, and nothing raises (what a parent of PR 38 gives)."""
    old = os.path.join(os.path.dirname(PROBE), "scope_probe.xplane.pb")
    monkeypatch.setattr(account, "newest_trace", lambda: old)
    serve = {"trace": {"busy_s": 1}, "kind": "serve",
             "peaks": harness.peaks_for("TPU v5 lite")}
    assert account.leaf_device_ms(serve, {"scope": "decode_attn"}) is None
    assert account.gap_ms(serve, {"phase": "read"}) is None
    assert account.unscoped_pct(serve, {}) is None
    train = {"trace": {"busy_s": 1}, "kind": "train", "trace_steps": 2}
    assert account.leaf_device_ms(train, {"scope": "attn_core"}) is None
    # its two old scopes are leaves of the vocabulary too
    assert account.leaf_device_ms(train, {"scope": "ssd_scan"}) == \
        pytest.approx(29_913_828e-9 / 2)


# ---------------------------------------------------------------------------
# the recorded probe
# ---------------------------------------------------------------------------
def test_the_probe_holds_five_ticks_with_their_children():
    assert os.path.getsize(PROBE) < 300_000
    line = account.timeline(PROBE)
    names = [n for _, _, n, _ in line["spans"]]
    assert names.count("tick") == 5 and names.count("prefill") == 1
    for child in ("tick/admit", "tick/launch", "tick/read", "tick/commit"):
        assert names.count(child) == 5
    ticks = account.launched_ticks(line["spans"])
    # three slots, then the admission of a 55-token prompt in the third
    assert [t["kv_positions"] for t in ticks] == [225, 228, 287, 291, 295]
    assert [t["active"] for t in ticks] == [3, 3, 4, 4, 4]
    prefill = next(st for _, _, n, st in line["spans"] if n == "prefill")
    assert prefill == {"bucket": 128, "prompt_tokens": 55}
    # every child lies inside a tick, and the prefill inside an admit
    segments = account.innermost_segments(line["spans"])
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    assert sum(n == "prefill" for _, _, n in segments) == 1


def test_the_probes_gaps_sum_to_its_idle_time():
    line = account.timeline(PROBE)
    busy = line["busy"]
    idle = sum(b[0] - a[1] for a, b in zip(busy, busy[1:]))
    window = busy[-1][1] - busy[0][0]
    assert idle == pytest.approx(window - sum(e - s for s, e in busy))
    cut = account.cut_gaps(busy, line["spans"])
    assert sum(cut.values()) == pytest.approx(idle)
    assert cut == {"read": 3847270.0, "host": 3879082.0,
                   "launch": 6606968.0, "outside": 2420690.0}
    # the caller slept 2 ms after the fourth tick: that is `outside`
    assert cut["outside"] > 2.0e6


def test_the_probes_operations_go_to_their_innermost_scope(monkeypatch):
    with open(PROBE, "rb") as f:
        ops = nemotron.device_ops(f.read())
    assert len(ops) == 881
    table = account.account(ops)
    total = lambda row: sum(row.values())
    assert set(table) == {
        "embed", "attn", "attn_proj", "attn_core", "decode_attn", "kv_write",
        "mlp", "head", "sample", account.UNSCOPED, account.PATHLESS}
    assert all(row["remat"] == row["bwd"] == 0 for row in table.values())
    assert total(table["decode_attn"]) == 40_308_438      # picoseconds
    assert total(table["kv_write"]) == 25_070_862
    assert total(table[account.PATHLESS]) == 16_718_436
    # the rows tile the device's own time
    assert sum(total(r) for r in table.values()) == 164_186_640 == \
        sum(own for _, own in account.own_times(ops))
    # the by-name reader finds a scope anywhere in a path, so the block
    # scope `attn` holds its inner scopes' time too: the old rule stands
    by_name = nemotron.own_time_by_scope(ops, ["attn", "decode_attn"])
    assert by_name["attn"] > total(table["attn"]) + by_name["decode_attn"]
    # through the readers, a launched tick at a time
    monkeypatch.setattr(account, "newest_trace", lambda: PROBE)
    obs = {"trace": {"busy_s": 1}, "kind": "serve",
           "peaks": harness.peaks_for("TPU v5 lite")}
    assert account.leaf_device_ms(obs, {"scope": "decode_attn"}) == \
        pytest.approx(40_308_438e-9 / 5)
    assert account.leaf_device_ms(obs, {"scope": "absent"}) is None
    assert account.leaf_device_ms(obs, {"pass": "remat"}) is None
    assert account.gap_ms(obs, {"phase": "launch"}) == \
        pytest.approx(6606968e-6 / 5)
    assert account.xla_made_pct(obs, {}) == pytest.approx(
        100 * 16_718_436 / 164_186_640)
    assert account.unscoped_pct(obs, {}) == pytest.approx(
        100 * 5_894_610 / 164_186_640)
    # the probe's model: 2 layers, 2 heads of 128
    monkeypatch.setattr(gpt, "_model", lambda params: {
        "num_layers": 2, "num_heads": 2, "hidden_size": 256})
    need = 2 * (225 + 228 + 287 + 291 + 295) * 2 * 128 * 2 * 2
    assert gpt.decode_attn_roofline_pct(obs, {}) == pytest.approx(
        100 * (need / 819e9) / 40_308_438e-12)
