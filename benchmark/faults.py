"""Faults planted under the served path, to show that the check sees them
(``benchmark/tests/test_run.py`` on the CPU, ``control.py --faults`` on
the chip). Each ``plant_*`` patches the program in place and returns a
function that undoes it.

- ``altered``: every 7th packet's disposition flipped where the device
  step produces it (``Dataplane.process_packed``);
- ``half``: the tx writer returns each frame with half its packets;
- ``lost``: every 5th frame the tx writer reports written never reaches
  the tx ring;
- ``stale``: every device step returns its state unchanged (the step's
  new tables are discarded, so no session is ever stored);
- ``backend0``: every VIP flow is sent to the VIP's first backend (the
  mapping's total weight read as 1, so the weighted pick always lands
  on the first).
"""

from __future__ import annotations

import numpy as np


def plant_altered(dp):
    orig = dp.process_packed

    def altered(flat, *a, **k):
        res = orig(flat, *a, **k)
        out, aux = res if isinstance(res, tuple) else (res, None)
        flip = np.zeros(out.shape, np.int32)
        flip[3, ::7] = 1 << 24      # the low disposition bit of row 3
        out = out ^ flip
        return (out, aux) if aux is not None else out

    dp.process_packed = altered
    return lambda: setattr(dp, "process_packed", orig)


def _patch_push(wrap):
    from vpp_tpu.io.rings import IORing

    orig = IORing.push_packed
    IORing.push_packed = wrap(orig)
    return lambda: setattr(IORing, "push_packed", orig)


def plant_half(dp=None):
    def wrap(orig):
        def half(self, packed, poff, n, *a, **k):
            return orig(self, packed, poff, n // 2, *a, **k)
        return half
    return _patch_push(wrap)


def plant_lost(dp=None):
    seen = [0]

    def wrap(orig):
        def lossy(self, *a, **k):
            seen[0] += 1
            if seen[0] % 5 == 0:
                return True
            return orig(self, *a, **k)
        return lossy
    return _patch_push(wrap)


def plant_stale(dp):
    orig = dp.process_packed

    def stale(flat, *a, **k):
        return orig(flat, *a, **dict(k, commit=False))

    dp.process_packed = stale
    return lambda: setattr(dp, "process_packed", orig)


def plant_backend0(dp):
    import jax.numpy as jnp

    with dp._lock:
        orig = dp.tables.nat_total_w
        dp.tables = dp.tables._replace(nat_total_w=jnp.ones_like(orig))

    def undo():
        with dp._lock:
            dp.tables = dp.tables._replace(nat_total_w=orig)
    return undo


PLANTS = {"altered": plant_altered, "half": plant_half, "lost": plant_lost,
          "stale": plant_stale, "backend0": plant_backend0}
