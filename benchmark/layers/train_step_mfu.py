"""``train_step_mfu``: the whole step's share of the chip's peak — the
operations the model requires for the tokens the traced window completed
(``roofline.train_flops_per_token``; recomputation not counted) per second,
over the peak of the chips used."""
from benchmark import reference, roofline


def read(run):
    c = run["counts"]
    if not c.get("steps"):
        return None
    s = reference.Sizes(run["config"])
    per_s = (roofline.train_flops_per_token(s, c["seq"])
             * c["steps"] * c["tokens_per_step"] / c["elapsed_s"])
    return 100.0 * per_s / (run["chips"] * run["peak"]["flops_per_s"])
