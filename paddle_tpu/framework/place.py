"""Device places.

Reference parity: paddle/fluid/platform/place.h (CPUPlace/CUDAPlace/...).
TPU-first: TPUPlace is the primary device; it resolves to a jax TPU device.
"""
import jax


class PlaceUnavailableError(RuntimeError):
    """The place names a backend (or a device index on it) this process
    does not have — e.g. TPUPlace in a CPU-only process."""


class Place(object):
    _backend = None

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def jax_device(self):
        """Resolve to a concrete jax.Device."""
        if self._backend is None:  # "best available" place
            return jax.devices()[self.device_id]
        try:
            devices = jax.devices(self._backend)
        except RuntimeError as e:
            raise PlaceUnavailableError(
                "%r needs a %r backend, but this process has only %r "
                "(default backend %r): %s"
                % (self, self._backend,
                   sorted({d.platform for d in jax.devices()}),
                   jax.default_backend(), e)) from e
        if self.device_id >= len(devices):
            raise PlaceUnavailableError(
                "%r: the %r backend has %d device(s)"
                % (self, self._backend, len(devices)))
        return devices[self.device_id]


class TPUPlace(Place):
    _backend = "tpu"


class CPUPlace(Place):
    _backend = "cpu"

    def __init__(self):
        super(CPUPlace, self).__init__(0)


class DefaultPlace(Place):
    """Whatever jax considers the default backend (TPU when attached)."""
    _backend = None


def _current_expected_place():
    # An active jax.default_device(...) pin (config or context manager) is
    # the caller's word on placement — honour it before consulting the
    # process-global backend list, so code running inside e.g. a CPU-pinned
    # dryrun never self-selects the attached TPU.
    pinned = getattr(jax.config, "jax_default_device", None)
    if pinned is not None:
        # jax accepts a Device object or a platform string here.
        platform = pinned if isinstance(pinned, str) \
            else getattr(pinned, "platform", None)
        if platform == "tpu":
            return TPUPlace(getattr(pinned, "id", 0))
        return CPUPlace()
    if jax.default_backend() == "tpu":
        return TPUPlace(0)
    return CPUPlace()
