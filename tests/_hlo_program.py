"""A compiled module's text reduced to its program: what runs, without
what only names it.

Left out: the ops' ``metadata`` (``op_name`` carries the named scopes a
device trace reads), the source-location tables that metadata indexes,
the debug locations inside each serialized Mosaic kernel (its ``body``
is printed again without them), and the names of instructions and
computations, which the TPU compiler derives in part from ``op_name``
(they are numbered in order of first appearance). Two modules with the
same text here run the same ops in the same order on the same
operands.
"""
import base64
import hashlib
import re

#: source-location tables of a module's text, which metadata indexes
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_BODY = re.compile(r'"body":"([^"]*)"')
_NAME = re.compile(r"%[\w.\-]+")


def _kernel(match):
    """A Mosaic kernel's serialized module, without its locations, as a
    digest."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        text = module.operation.get_asm(enable_debug_info=False)
    return '"body":"' + hashlib.sha256(text.encode()).hexdigest() + '"'


def program(hlo):
    blocks = [b for b in hlo.split("\n\n")
              if b.split("\n", 1)[0] not in TABLES]
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))
    text = _BODY.sub(_kernel, text)
    names = {}
    return _NAME.sub(lambda m: names.setdefault(m.group(0),
                                                f"%v{len(names)}"), text)
