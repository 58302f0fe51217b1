"""``ssm_outside_kernel_share``: the share of the device's busy time that
the state-space mixer takes outside its Mosaic scan: the instructions under
the scope ``ssm`` that are not a Mosaic call (the projections, the
convolution, the padded layout's copies around ``ssd_scan``).
``ssd_scan_device_share`` is the other part of the mixer."""
from benchmark import scope_share


def read(run):
    return scope_share.share(run, under=("ssm",), mosaic=False)
