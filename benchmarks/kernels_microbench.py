"""Pallas-kernel microbenchmarks, kernel vs jnp reference.

The kernels run compiled on TPU and in interpret mode elsewhere
(:func:`repro.kernels.ops.default_interpret`).  Every row names the
device that ran it (``platform:device_kind``, plus ``interpret`` when the
interpreter ran): an interpret-mode wall time says how fast the Pallas
interpreter is, not the TPU.  The jnp reference checks numerical parity
at the benchmark shapes.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timed
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lap_bid import lap_bid_pallas
from repro.kernels.migration_cost import migration_cost_pallas
from repro.kernels.ops import default_interpret


def main(print_csv: bool = True) -> List[str]:
    rows: List[str] = []
    rng = np.random.default_rng(0)
    interp = default_interpret()
    dev = jax.devices()[0]
    device = f"{dev.platform}:{dev.device_kind}" + (";interpret" if interp else "")

    a = jnp.asarray(rng.normal(size=(512, 512)), jnp.float32)
    p = jnp.zeros((512,), jnp.float32)
    lap_bid_pallas(a, p, interpret=interp)  # compile
    _, t = timed(lambda: lap_bid_pallas(a, p, interpret=interp)[0].block_until_ready())
    rows.append(csv_row("kernels/lap_bid_512", t * 1e6, device))

    su = jnp.asarray(rng.integers(-1, 40, size=(256, 2)), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 0.5, size=(256, 2)), jnp.float32)
    migration_cost_pallas(su, su, w, w, interpret=interp)
    _, t = timed(
        lambda: migration_cost_pallas(su, su, w, w, interpret=interp).block_until_ready()
    )
    rows.append(csv_row("kernels/migration_cost_256", t * 1e6, device))

    q = jnp.asarray(rng.normal(size=(4, 512, 128)), jnp.bfloat16)
    flash_attention_pallas(q, q, q, interpret=interp)
    _, t = timed(
        lambda: flash_attention_pallas(q, q, q, interpret=interp).block_until_ready()
    )
    got = flash_attention_pallas(q, q, q, interpret=interp)
    want = ref.flash_attention(q, q, q)
    err = float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    )
    rows.append(csv_row("kernels/flash_attn_4x512x128", t * 1e6, f"{device};max_err={err:.4f}"))

    from repro.kernels.flash_decode import flash_decode_pallas

    q1 = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(2, 2048, 2, 128)), jnp.bfloat16)
    flash_decode_pallas(q1, kc, kc, jnp.asarray(2048), interpret=interp)
    _, t = timed(
        lambda: flash_decode_pallas(
            q1, kc, kc, jnp.asarray(2048), interpret=interp
        ).block_until_ready()
    )
    gd = flash_decode_pallas(q1, kc, kc, jnp.asarray(2048), interpret=interp)
    wd = ref.flash_decode(q1, kc, kc, 2048)
    errd = float(jnp.max(jnp.abs(gd.astype(jnp.float32) - wd.astype(jnp.float32))))
    rows.append(csv_row("kernels/flash_decode_2x8x2048", t * 1e6, f"{device};max_err={errd:.4f}"))
    if print_csv:
        for r in rows:
            print(r)
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
