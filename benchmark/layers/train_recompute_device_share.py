"""``train_recompute_device_share``: the share of the device's busy time
that the replay of a ``jax.checkpoint`` took: the instructions of the train
step whose path lies under ``rematted_computation`` (Mosaic calls included:
``flash_fwd``'s second run).  What full recomputation costs, where
``train_step_mfu`` only infers it."""
from benchmark import scope_share


def read(run):
    return scope_share.share(run, passes=("recompute",))
