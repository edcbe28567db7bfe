"""The program-span reader and the three metrics that read it, on a small
recorded trace of the serving loop's own spans (data/trace_spans.pbtxt: one
admission, the decode block after it and the interval scrub after that), and
on the trace of a program without them (data/trace_slice.pbtxt), where every
one of them reports nothing."""

from __future__ import annotations

import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import registry, spans, tracing  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
READERS = ("scrub_live_page_share", "scrub_us_per_live_page", "decode_host_us_per_block")
# data/trace_spans.pbtxt, read by hand: its window, and the spans the
# readers use (start and end in ns on the trace's clock)
WINDOW = (18092384, 25151291)
BLOCK = (20807902, 23277942)  # serve.decode_block, k 1, 2 lanes
BLOCK_SYNC = (21570445, 23242007)  # serve.block_sync inside it
SCRUB_PAGES, SCRUB_LIVE = 4, 3  # kv.paged_gather_scrub: 2 lanes x 2 columns
SCRUB_DEVICE_NS = 6_000_000  # the fixture has no device plane: given here


def _serialized(name):
    import jax

    with open(os.path.join(DATA, name)) as f:
        return jax.profiler.ProfileData.text_proto_to_serialized_xspace(f.read())


def _profile(name):
    import jax

    return jax.profiler.ProfileData.from_serialized_xspace(_serialized(name))


@pytest.fixture(scope="module")
def profile():
    return _profile("trace_spans.pbtxt")


@pytest.fixture(scope="module")
def reduced():
    """What ``tracing.reduce`` gives for the fixture's window, with the
    scrub program's device time that a chip's trace would carry."""
    return tracing.Reduced(WINDOW, 1, 0.0, {}, {"jit__scrub_rows": SCRUB_DEVICE_NS}, [], [])


def _read(name, reduced, sp):
    return registry.metric_reader(name, ROOT)({"reduced": reduced, "spans": sp})


def test_program_spans_are_the_windows_program_events_in_order(profile):
    sp = spans.program_spans(profile, WINDOW)
    assert [n for n, *_ in sp] == [
        "serve.admit", "serve.prefill_group", "decode.prefill", "kv.commit_tokens",
        "serve.prefill_sync", "serve.decode_block", "serve.page_growth", "decode.multistep",
        "serve.block_sync", "serve.scrub_harvest", "serve.scrub_sync", "serve.scrub_dispatch",
        "kv.paged_gather_scrub",
    ]
    assert all(WINDOW[0] <= s <= e <= WINDOW[1] for _, s, e, _ in sp)
    assert spans.program_spans(profile) == sp  # bench.window is not a program span
    (block,) = spans.named(sp, "serve.decode_block")
    assert block[1:] == (*BLOCK, {"k": 1, "lanes_active": 2})
    assert [s[0] for s in spans.inside(sp, block)] == [
        "serve.decode_block", "serve.page_growth", "decode.multistep", "serve.block_sync",
    ]
    (scrub,) = spans.named(sp, "kv.paged_gather_scrub")
    assert scrub[3] == {"pages": SCRUB_PAGES, "live_pages": SCRUB_LIVE}
    # a window that cuts a span keeps only what lies wholly inside it
    assert [n for n, *_ in spans.program_spans(profile, BLOCK)] == [
        "serve.decode_block", "serve.page_growth", "decode.multistep", "serve.block_sync",
    ]


def test_readers_give_the_numbers_worked_out_by_hand(profile, reduced):
    sp = spans.program_spans(profile, WINDOW)
    assert _read("scrub_live_page_share", reduced, sp) == pytest.approx(100.0 * 3 / 4)
    assert _read("scrub_us_per_live_page", reduced, sp) == pytest.approx(6_000_000 / 3 / 1e3)
    host_ns = (BLOCK[1] - BLOCK[0]) - (BLOCK_SYNC[1] - BLOCK_SYNC[0])
    assert host_ns == 798_478
    assert _read("decode_host_us_per_block", reduced, sp) == pytest.approx(798.478)


def test_readers_report_nothing_for_a_program_without_spans():
    """The chip trace of a program that opens no spans (trace_slice.pbtxt):
    no program span, and each reader returns None instead of raising."""
    pd = _profile("trace_slice.pbtxt")
    red = tracing.reduce(pd)
    assert spans.program_spans(pd, red.window) == []
    for name in READERS:
        assert _read(name, red, []) is None, name


@pytest.mark.parametrize("fixture", ["trace_spans.pbtxt", "trace_slice.pbtxt"])
def test_readers_find_the_profile_the_harness_left(tmp_path, fixture):
    """Given no ``ctx["spans"]``, the readers of a checkout read the newest
    profile under its ``.bench_trace/``, where ``bench/run.py --trace 1``
    writes it, and agree with the spans handed to them."""
    data = _serialized(fixture)
    pd = _profile(fixture)
    red = (
        tracing.reduce(pd)
        if fixture == "trace_slice.pbtxt"
        else tracing.Reduced(WINDOW, 1, 0.0, {}, {"jit__scrub_rows": SCRUB_DEVICE_NS}, [], [])
    )
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "bench" / "metrics")
    trace = root / ".bench_trace" / "cell" / "plugins" / "profile" / "1"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(data)
    sp = spans.program_spans(pd, red.window)
    reader_file = str(root / "bench" / "metrics" / "any.py")
    assert spans.window_spans({"reduced": red}, reader_file) == sp
    for name in READERS:
        got = registry.metric_reader(name, str(root))({"reduced": red})
        assert got == _read(name, red, sp), name
    shutil.rmtree(root / ".bench_trace")
    assert spans.window_spans({"reduced": red}, reader_file) == []
