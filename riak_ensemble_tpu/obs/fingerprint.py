"""Box fingerprint: the environment a measurement actually ran in.

The r4→r5 mixed-rung comparison went sideways because two rounds'
numbers were silently captured on differently-loaded boxes (the same
runner executed r5's mixed rung at ~4× r4's per-batch time).  Every
flight-recorder dump and every bench JSON now embeds this
fingerprint, so a cross-round delta can be checked against the box
before it is believed.

Static fields (host, cpu count, versions, knobs) are cached;
load-dependent fields (loadavg) are re-read per call.  jax/jaxlib
versions come from package metadata, NOT ``import jax`` — the
fingerprint must never be the thing that initializes a backend (a
launcher parent has to stay off the chip its children need).  The
device fields therefore read only a backend that is ALREADY up, and
are None in a process that never touched one.
"""

from __future__ import annotations

import os
import platform
import socket
import sys
from typing import Any, Dict

__all__ = ["box_fingerprint"]

_static: Dict[str, Any] = {}


def _pkg_version(name: str) -> str:
    try:
        from importlib.metadata import version
        return version(name)
    except Exception:
        return "unknown"


def _device() -> Dict[str, Any]:
    """What jax actually runs on in this process — the env var only
    says what was asked for."""
    jax = sys.modules.get("jax")
    if jax is None or not jax._src.xla_bridge.backends_are_initialized():
        return {"device_platform": None, "device_kind": None,
                "device_count": None}
    devs = jax.devices()
    return {"device_platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def box_fingerprint() -> Dict[str, Any]:
    """A plain JSON-able dict identifying the box + software + knob
    state.  Cheap after the first call."""
    if not _static:
        _static.update({
            "schema": "retpu-box-fingerprint-v1",
            "hostname": socket.gethostname(),
            "platform": platform.platform(),
            "machine": platform.machine(),
            "python": sys.version.split()[0],
            "cpu_count": os.cpu_count(),
            "jax": _pkg_version("jax"),
            "jaxlib": _pkg_version("jaxlib"),
            "numpy": _pkg_version("numpy"),
        })
    out = dict(_static)
    try:
        la1, la5, la15 = os.getloadavg()
        out["loadavg"] = [round(la1, 2), round(la5, 2),
                          round(la15, 2)]
    except (OSError, AttributeError):
        out["loadavg"] = None
    out["jax_platforms_env"] = os.environ.get("JAX_PLATFORMS")
    out.update(_device())
    out["retpu_knobs"] = {k: v for k, v in os.environ.items()
                          if k.startswith("RETPU_")}
    return out
