"""KV store: share of the interval scrub's page-table entries that hold a
live page.

The scrub gathers every entry of its ``lanes x p_cols`` table alike, where
``p_cols`` is the longest lane's page count rounded up to a power of two and
idle lanes and short lanes are filled with the scratch page. Each
``kv.paged_gather_scrub`` span of the traced window carries ``pages`` (table
entries) and ``live_pages`` (entries that are not the scratch page); the
share is the sum of ``live_pages`` over the sum of ``pages``. A count, so it
is the same in every run of a cell.
"""

from benchlib import spans


def read(ctx):
    scrubs = spans.named(spans.window_spans(ctx, __file__), "kv.paged_gather_scrub")
    pages = sum(st.get("pages", 0) for _, _, _, st in scrubs)
    if not pages:
        return None
    return 100.0 * sum(st.get("live_pages", 0) for _, _, _, st in scrubs) / pages
