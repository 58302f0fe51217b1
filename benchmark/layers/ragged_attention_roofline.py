"""``ragged_attention_roofline``: the least time the chip could take for the
attention the window's steps require — every step reads the cached keys and
values of every live row in every layer, and multiplies each processed token
against its context — over the device time the trace gives the Mosaic
custom calls of the serving step (it has one Pallas kernel, the ragged paged
attention; the trace names the call after its jax scope, not its kernel)."""
from benchmark import reference, roofline


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr:
        return None
    spent = sum(sec for name, sec in tr["ops"].items()
                if "[mosaic]" in name)
    if spent <= 0:
        return None
    s = reference.Sizes(run["config"])
    # keys and values of every cached position of every live row, bf16
    nbytes = c["context_rows"] * 2 * s.D * 2 * s.L
    tokens = c["prefill_tokens"] + c["generated_tokens"]
    ops = 4 * c["mean_context"] * s.D * tokens * s.L
    least, _ = roofline.least_seconds(ops, nbytes, run["peak"])
    return 100.0 * least / spent
