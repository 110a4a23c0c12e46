"""Whether a Pallas kernel entry runs in interpret mode when its caller
does not say."""
import os


def default_interpret():
    """interpret-mode default shared by every kernel entry: honor
    PADDLE_TPU_PALLAS_INTERPRET, else interpret off-TPU — decided from
    the EFFECTIVE default device, not the process backend list (a
    jax.default_device(cpu) pin routes this computation to CPU even when
    a chip is attached)."""
    env = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "")
    import jax
    pinned = getattr(jax.config, "jax_default_device", None)
    if pinned is None:
        platform = jax.default_backend()
    elif isinstance(pinned, str):
        platform = pinned
    else:
        platform = getattr(pinned, "platform", None)
    return platform != "tpu"
