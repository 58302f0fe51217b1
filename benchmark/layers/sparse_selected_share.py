"""``sparse_selected_share``: positions the sparse layers read over the
positions the processed tokens had in context, per cent — the program's
``serving_attention_positions_total{kind="selected"}`` over
``{kind="context"}``, both counted on the host from the lengths.  100 for
a model that reads everything; the lower, the more the selection saves."""


def read(run):
    c = run["counts"]
    if not c.get("context_positions"):
        return None
    return 100.0 * c["selected_positions"] / c["context_positions"]
