"""``device_scope_coverage.train``: the share of the device's busy time in
the traced window whose instruction is in the train step's table
(``hybrid_engine::step``) under at least one ``jax.named_scope``: how much
of the device's time the program can name.  The instrument's own health:
what is left is other programs of the window and instructions that carry no
scope."""
from benchmark import scope_share


def read(run):
    return scope_share.share(run, under=scope_share.ANY)
