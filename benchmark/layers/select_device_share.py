"""``select_device_share``: the share of the device's busy time under the
scope ``select``: scoring the compressed keys and ranking the blocks
(``lax.top_k``) in the sparse layers.  The kernel's work list is under
``work_list`` and the compressed keys' write under ``kv_write``: neither is
in it."""
from benchmark import scope_share


def read(run):
    return scope_share.share(run, under=("select",))
