"""Backend probe for the Pallas kernels' ``interpret`` switch."""
import jax


def interpret_default() -> bool:
    """Pallas ``interpret`` switch resolved from the backend at trace
    time: compile to Mosaic on TPU, run the interpreter everywhere else
    (CPU tests, CI). Kernel entry points take ``interpret=None`` and
    resolve through here, so no call site hardcodes ``True``."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> the backend default; an explicit bool wins. The one
    place every kernel's ``pallas_call`` threads its ``interpret``
    through."""
    return interpret_default() if interpret is None else interpret
