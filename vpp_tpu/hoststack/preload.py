"""Build + launch helpers for the LD_PRELOAD session shim.

``shim_path()`` compiles native/vcl_preload.c into libvclshim.so with
the same on-demand machinery as the other native libraries;
``vcl_env()`` returns the environment an unmodified app needs so its
connect()/accept() calls are admission-checked against the node's
session rules (the reference's ldpreload deployment shape: the CRI shim
injects exactly these env vars into pod containers,
cmd/contiv-cri + tests/ld_preload*).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from vpp_tpu.native.ring import build_native, native_lib_path

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "native", "vcl_preload.c")


def shim_path(force: bool = False) -> str:
    """Compile-if-missing; returns the absolute path of the shim,
    ``libvclshim-<sha>.so`` keyed by the source content."""
    return build_native(_SRC, native_lib_path(_SRC, "libvclshim"), force)


def vcl_env(
    admission_sock: str,
    appns_index: int = 0,
    fail_closed: bool = False,
    base: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """Environment for launching an app under the session shim.

    Appends to (a copy of) ``base`` or os.environ: LD_PRELOAD chains
    after any existing preloads.
    """
    env = dict(os.environ if base is None else base)
    lib = shim_path()
    prior = env.get("LD_PRELOAD", "")
    env["LD_PRELOAD"] = f"{prior}:{lib}" if prior else lib
    env["VPP_TPU_VCL_SOCK"] = admission_sock
    env["VPP_TPU_APPNS"] = str(int(appns_index))
    if fail_closed:
        env["VPP_TPU_VCL_FAILCLOSED"] = "1"
    else:
        env.pop("VPP_TPU_VCL_FAILCLOSED", None)
    return env
