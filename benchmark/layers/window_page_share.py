"""``window_page_share``: pages the window layers' pools held over what
tables that never give a page back would hold for the same rows, per cent —
the program's ``serving_pages_in_use{pool="window"}`` over ``{pool="full"}``
(a full layer's table *is* the unbounded one), each summed over the
window's steps.  100 for a model that keeps every position; the lower, the
more the window saves."""


def read(run):
    c = run["counts"]
    if not c.get("full_pages_held"):
        return None
    return 100.0 * c["window_pages_held"] / c["full_pages_held"]
