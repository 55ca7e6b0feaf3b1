"""SyncAgtr gradient services on the two register-file lanes.

``DeviceGrad`` keeps its segment on the device (``device=True``: fused
quantize + saturating addTo, and gather + dequantize on the reply);
``HostGrad`` is the same service on the numpy register file. The chip
benchmark's ``syncagtr_32x40k`` configuration names ``DeviceGrad`` as the
service its serve cells drive. Speed is measured by ``bench/run.py`` on
the chip; the two lanes' element-exact agreement is checked by
``tests/test_device_path.py``.
"""
from __future__ import annotations

import repro.api as inc


@inc.service(app="DEVP-dev", name="DeviceGrad")
class DeviceGrad:
    @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
    def Update(self, tensor: inc.Agg[inc.FPArray](
            precision=6, clear="copy", device=True)
            ) -> {"tensor": inc.Get[inc.FPArray]}: ...


@inc.service(app="DEVP-host", name="HostGrad")
class HostGrad:
    @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
    def Update(self, tensor: inc.Agg[inc.FPArray](
            precision=6, clear="copy")
            ) -> {"tensor": inc.Get[inc.FPArray]}: ...
