"""``experts_outside_kernel_share``: the share of the device's busy time
that the expert layer takes outside its Mosaic products: the instructions
under the scope ``experts`` that are not a Mosaic call (the rank, the
scatter into the sorted buffer, the row gathers, the weighted combine).
``expert_matmul_device_share`` is the other part of the layer."""
from benchmark import scope_share


def read(run):
    return scope_share.share(run, under=("experts",), mosaic=False)
