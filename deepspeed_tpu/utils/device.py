"""The one device-platform probe.

Every Pallas call site picks compiled vs interpret mode from this, and
the flash dispatchers pick kernel vs XLA path from it. It does not catch:
a backend that fails to initialize is an error to surface, not a reason
to run the interpreter (or the O(S^2) path) as if nothing happened.
Call sites go through the module (``device.on_tpu()``) so a compile
rehearsal for a described-but-unattached chip can steer all of them with
one patch.
"""

import jax


def on_tpu():
    return jax.devices()[0].platform == "tpu"


def describe():
    """The accelerator as jax reports it — the three keys every result
    line of benchmark/run.py and chip_smoke.py carries."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def host_cpu_device():
    """The host CPU device beside the accelerator (host-side parameter
    init, the ZeRO-Offload state). With ``JAX_PLATFORMS`` unset a TPU
    host has both backends; pinning ``JAX_PLATFORMS=tpu`` removes the CPU
    one, and that is named here instead of surfacing as a bare backend
    lookup error."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "no CPU backend beside the accelerator: JAX_PLATFORMS="
            f"{jax.config.jax_platforms!r} excludes 'cpu'. Leave "
            "JAX_PLATFORMS unset (or use 'tpu,cpu') for host-side "
            "parameter init and optimizer offload"
        ) from e
