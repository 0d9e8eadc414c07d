"""The one place the program asks which device it runs on.

Two platforms are supported: a TPU (kernels compile through Mosaic) and the
CPU (tests; kernels run in interpret mode). Anything else raises: a silent
"not a TPU, so take the XLA math" answer is how a broken chip path hides.
Errors from JAX itself (no backend, plugin failed to start) propagate.
"""

from __future__ import annotations

SUPPORTED = ("tpu", "cpu")


def device_platform() -> str:
    """`jax.devices()[0].platform`, held to the supported set."""
    import jax

    platform = jax.devices()[0].platform
    if platform not in SUPPORTED:
        raise RuntimeError(
            f"unsupported JAX platform {platform!r}: llm-mcp-tpu runs on "
            f"{' or '.join(SUPPORTED)}"
        )
    return platform


def on_tpu() -> bool:
    return device_platform() == "tpu"
