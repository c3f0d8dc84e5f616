"""The serving runner's end-to-end arithmetic, the train runner's window
condition, and the refusal of a traced line that lacks a listed metric, but
for one whose span the program does not write at all."""

import pytest

from perfbench.manifest import ROOT, Manifest
from perfbench.runners.common import every_listed_metric
from perfbench.runners.serve import end_to_end
from perfbench.runners.train import (WINDOW_END, WINDOW_LOW,
                                     window_moved)

MAN = Manifest(ROOT)
SUMMARY = {"ttft_ms": [100.0, 200.0, 300.0, 400.0, 1000.0],
           "gaps_ms": [90.0, 100.0, 110.0, 500.0],
           "latency_ms": [1000.0, 4000.0, 500.0], "tokens_got": 50,
           "out_tokens": 250, "seconds": 50.0}


def test_the_tails_and_the_rate_are_over_all_the_window():
    e2e = end_to_end(SUMMARY)
    assert e2e["out_tokens_per_s"] == pytest.approx(5.0)
    assert e2e["itl_p99_ms"] == pytest.approx(488.3)
    assert e2e["ttft_p95_ms"] == pytest.approx(880.0)
    # all the waiting, due instant to last token, over all the tokens got:
    # weighted by tokens, not a mean of the requests' own ratios
    assert e2e["latency_ms_per_out_token"] == pytest.approx(5500.0 / 50)
    assert set(e2e) == {"ttft_p95_ms", "itl_p99_ms", "out_tokens_per_s",
                        "latency_ms_per_out_token"}


def test_no_gap_and_no_first_token_give_no_tail():
    e2e = end_to_end({**SUMMARY, "gaps_ms": [], "ttft_ms": [],
                      "latency_ms": [], "tokens_got": 0})
    assert set(e2e) == {"out_tokens_per_s"}


def test_every_cell_reports_an_end_to_end_metric_the_runner_computes():
    """The chat cell's is ``latency_ms_per_out_token`` since the driver's
    check of PR 27 refused ``ttft_p95_ms`` as too noisy for any bound; the
    tail stands per layer under another name. Each serving metric of the
    manifest is one ``end_to_end`` yields."""
    have = set(end_to_end(SUMMARY)) | {"setup_s"}
    for cell in MAN.doc["workloads"]:
        if cell["name"].startswith("serve-"):
            names = {m["name"] for m in MAN.metrics_for(cell["name"],
                                                        "end_to_end")}
            assert names <= have and len(names) >= 2
    chat = {m["name"] for m in MAN.metrics_for("serve-chat-steady",
                                               "end_to_end")}
    assert chat == {"latency_ms_per_out_token", "setup_s"}
    layer = {m["name"]: m for m in MAN.metrics_for("serve-chat-steady",
                                                   "per_layer")}
    assert {"ttft_p95_ms.chat", "ttft_p50_ms.chat"} <= set(layer)
    assert all(m["moves"] in chat for m in layer.values())


def test_the_first_tokens_tail_is_read_per_layer():
    ctx = {"summary": SUMMARY}
    assert MAN.reader("ttft_p95_ms.chat")(ctx) == pytest.approx(880.0)
    assert MAN.reader("ttft_p50_ms.chat")(ctx) == pytest.approx(300.0)
    none = {"summary": {**SUMMARY, "ttft_ms": []}}
    assert MAN.reader("ttft_p95_ms.chat")(none) is None
    assert MAN.reader("ttft_p50_ms.chat")(none) is None


#: as read on the chip (PR 27): a plateau that oscillates; a run whose loss
#: jumps most of the way back for single steps (seed 3321000037, whole)
PLATEAU = [10.8897, 8.114, 7.5084, 7.1, 6.7243, 7.3, 7.6, 7.5299]
JUMPY = [10.868, 8.379, 9.125, 8.330, 10.092, 8.174, 8.765, 8.166, 7.660,
         7.781, 8.307, 8.723, 8.078, 8.462, 8.085, 8.041, 9.720, 7.853, 8.331,
         8.130, 7.934, 7.657, 7.455, 7.264, 6.957, 6.399, 5.958]


@pytest.mark.parametrize("losses,warm,want", [
    (PLATEAU, 2, True),                       # a plateau inside the window
    (JUMPY, 2, True),
    (JUMPY[:-1] + [10.5], 2, True),           # ... ending on one more spike
    # the state handed back unchanged all through the window
    ([10.41, 8.2, 7.5, 7.5, 7.5, 7.5], 2, False),
    # ... or in its first step only, after a sound warm-up
    ([10.41, 8.2, 7.5, 7.5, 7.1, 6.9], 3, False),
    # ... or in one step in its middle
    ([10.41, 8.2, 7.5, 7.1, 7.1, 6.9], 2, False),
    # the warm-up fell and the window climbs back: the lowest is not low
    ([10.41, 8.2, 8.9, 9.6, 10.2], 2, False),
    # low once, then back where it began, and it stays there
    ([10.41, 8.2, 7.5, 9.0, 10.1, 10.3, 10.2, 10.35, 10.3], 2, False),
    ([10.41, 8.2, float("nan"), 7.5], 2, False),
    ([float("inf"), 8.2, 7.5, 7.0], 2, False),
    # a fall that stops short of the limit
    ([10.0, 9.5, 9.2, 9.0, 8.9, 8.85], 2, False),
    ([10.0, 9.5, 9.2, 9.0, 8.0, 7.99], 2, True),
    ([10.41, 8.2], 2, False),                 # no window step at all
])
def test_the_train_cells_window_condition(losses, warm, want):
    """It looks at the timed window's own steps: each moves the state, the
    lowest lies under ``WINDOW_LOW`` of the loss at the seeded weights and
    the mean of the last five under ``WINDOW_END`` of it."""
    pairs = window_moved(losses, losses[warm:])
    assert all(ok for _, ok in pairs) is want
    assert len(pairs) == 4 and all(isinstance(t, str) for t, _ in pairs)


def test_the_limit_is_printed_with_the_reading():
    texts = [t for t, _ in window_moved(PLATEAU, PLATEAU[2:])]
    assert f"{WINDOW_LOW * PLATEAU[0]:.4f}" in texts[2] and "6.7243" in texts[2]
    assert f"{WINDOW_END * PLATEAU[0]:.4f}" in texts[3]


def _line(cell, without=()):
    return {m["name"]: {"value": 1.0, "unit": m["unit"]}
            for m in MAN.metrics_for(cell, "per_layer")
            if m["name"] not in without}


@pytest.mark.parametrize("cell", [w["name"] for w in MAN.doc["workloads"]])
def test_a_whole_traced_line_passes(cell):
    every_listed_metric(MAN, cell, _line(cell))


@pytest.mark.parametrize("lacking", ["prefill_device_ms_per_prompt_token",
                                     "admit_scatter_device_idle_pct"])
def test_a_traced_chat_line_without_an_admission_is_no_result(lacking):
    """The two readers that need an admission inside the traced stretch:
    where one comes up empty the run exits non-zero and names it."""
    with pytest.raises(SystemExit) as e:
        every_listed_metric(MAN, "serve-chat-steady",
                            _line("serve-chat-steady", without=(lacking,)))
    assert lacking in str(e.value) and e.value.code not in (0, None)


def test_a_metric_whose_span_the_program_lacks_is_left_out_by_name(capsys):
    """What ``layer_values`` reports of the parent under a PR's new reader:
    the line goes out without that metric, which standard error names."""
    lacking, cell = "step_prepare_ms", "serve-decode-heavy"
    every_listed_metric(MAN, cell, _line(cell, without=(lacking,)),
                        {lacking: ["serve.step.prepare"]})
    err = capsys.readouterr().err
    assert f"{cell}: {lacking} is left out of the line" in err
    assert "serve.step.prepare" in err
    # the say-so covers that metric and no other
    with pytest.raises(SystemExit) as e:
        every_listed_metric(
            MAN, cell, _line(cell, without=(lacking, "step_fetch_ms")),
            {lacking: ["serve.step.prepare"]})
    assert "step_fetch_ms" in str(e.value) and lacking not in str(e.value)


@pytest.mark.parametrize("cell,share", [
    ("serve-decode-heavy", "decode_hbm_roofline_pct"),
    ("serve-commanda-mixed-ctx-decode", "commanda_decode_hbm_roofline_pct"),
    ("train-fsdp2-tp2", "train_mfu_pct")])
def test_a_share_of_a_roofline_or_of_the_peak_is_never_left_out(cell, share,
                                                                capsys):
    with pytest.raises(SystemExit) as e:
        every_listed_metric(MAN, cell, _line(cell, without=(share,)),
                            {share: ["a.span.the_program_lacks"]})
    assert share in str(e.value) and e.value.code not in (0, None)
    assert "left out" not in capsys.readouterr().err
