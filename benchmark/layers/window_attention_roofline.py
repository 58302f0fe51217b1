"""``window_attention_roofline``: the least time the chip could take for the
attention of the window's steps in a model with sliding-window layers beside
full ones — reading, in each layer and step, the keys and values each live
row must have read (its whole context in a full layer, what its window
reaches in a sliding one) and the two products over the positions the
program counted for each kind — over the device time of the Mosaic calls
named ``ragged_paged_attention`` (the window layers' calls,
``ragged_paged_attention_window``, among them)."""
from benchmark import (kernel_share, reference_moe_window, roofline,
                       roofline_moe_window)


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr or "window_rows" not in c:
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"],
                                        ("ragged_paged_attention",))
    if spent <= 0:
        return None
    s = reference_moe_window.Sizes(run["config"])
    least, _ = roofline.least_seconds(
        roofline_moe_window.attention_ops(s, c["context_positions"],
                                          c["selected_positions"]),
        roofline_moe_window.attention_bytes(s, c["context_rows"],
                                            c["window_rows"]),
        run["peak"])
    return 100.0 * least / spent
