"""The Pallas kernels compile for a TPU v5e — checked on the CPU host.

No chip is needed: ``tools.tpu_aot`` lowers against
``jax.experimental.topologies``' description of a v5e host and runs the
installed libtpu's compiler, Mosaic included, with the kernel path forced
to Mosaic through the kernels' explicit ``path=`` argument.  This keeps
"the kernels compile at real shapes" true on every PR at no chip cost;
that they *compute* the right thing on the chip is ``chip_smoke.py``.
"""
import pytest

pytest.importorskip("libtpu")

from tools import tpu_aot  # noqa: E402


def test_kernels_compile_for_v5e():
    device = tpu_aot.topology_devices()[0]
    assert (device.platform, device.device_kind) == ("tpu", "TPU v5 lite")
    compiled = tpu_aot.compile_kernels(device)
    assert set(compiled) == {"ragged_q64", "ragged_q1", "flash_hd64",
                             "flash_hd128"}
    for name, c in compiled.items():
        assert "tpu_custom_call" in c.as_text(), \
            f"{name}: no Mosaic kernel in the compiled program"
