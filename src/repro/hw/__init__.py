"""Hardware target descriptions used by the Tuna static cost models.

Each target is a plain dataclass of published datasheet constants — no
measurement is required to instantiate one (the paper's cross-compilation
constraint).
"""
from repro.hw.target import HardwareTarget, FunctionalUnit
from repro.hw.tpu_v5e import TPU_V5E
from repro.hw.cpu_avx2 import CPU_AVX2
from repro.hw.gpu_a100 import GPU_A100

TARGETS = {t.name: t for t in (TPU_V5E, CPU_AVX2, GPU_A100)}

# ``device_kind`` as JAX reports it -> the target that describes that chip.
# A kind missing here is an error: schedules tuned for another chip's
# descriptor are never applied to it silently.
DEVICE_KINDS = {"TPU v5 lite": "tpu_v5e"}


def get_target(name: str) -> HardwareTarget:
    try:
        return TARGETS[name]
    except KeyError:
        raise KeyError(f"unknown hardware target {name!r}; have {sorted(TARGETS)}")


def target_for_device_kind(kind: str) -> HardwareTarget:
    try:
        return TARGETS[DEVICE_KINDS[kind]]
    except KeyError:
        raise ValueError(f"no hardware target describes device kind {kind!r}; "
                         f"known kinds: {sorted(DEVICE_KINDS)}") from None


def resolve_target(name=None) -> HardwareTarget:
    """The named target, or else the one describing ``jax.devices()[0]``
    (an error on a device the table does not know, the CPU included)."""
    if name is not None:
        return get_target(name)
    import jax  # deferred: tuning workers import this package and stay jax-free

    return target_for_device_kind(jax.devices()[0].device_kind)
