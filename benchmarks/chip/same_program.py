#!/usr/bin/env python3
"""Check that two checkouts compile a cell to the same programs, but for
metadata: a change that only names or annotates code.

    python3 benchmarks/chip/same_program.py dump <out_dir> <cell>...
    python3 benchmarks/chip/same_program.py diff <a.txt.gz> <b.txt.gz>

``dump``, from the root of a checkout on a machine with the cell's chips,
compiles each cell's prefill and decode as a run of the cell does and
writes their texts to ``<out_dir>/<cell>_<program>.txt.gz``.  Give it an
empty ``JAX_COMPILATION_CACHE_DIR``: the cache's key leaves out metadata,
so a program cached from the other checkout would be loaded with that
checkout's metadata and compare equal whatever the compiler does.

``diff`` prints the lines in which two such texts differ once the
operations' metadata, the ``HloModule`` line and the stack-frame tables are
removed and each Pallas kernel's Mosaic body is printed without its debug
locations (the source paths of the checkout it was built from).  It exits
1 where they differ.
"""

import base64
import difflib
import gzip
import re
import sys
from pathlib import Path

BODY = re.compile(r'(\\?"body\\?":\\?")([A-Za-z0-9+/=]+)')
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def dump(out: Path, cells) -> None:
    root = Path.cwd()
    sys.path.insert(0, str(root))
    from benchmarks.chip import harness

    harness.import_program(root)
    harness.enable_compile_cache()
    out.mkdir(parents=True, exist_ok=True)
    for name in cells:
        cell = harness.load_cell(root, name)
        devices, _, _ = harness.devices_for(cell,
                                            root / harness.BENCH / "peaks.json")
        sess = harness.Session(cell, devices)
        for program in ("prefill", "decode"):
            with gzip.open(out / f"{name}_{program}.txt.gz", "wt") as f:
                f.write(getattr(sess, program).as_text())
        print(f"{name}: {sess.prefill_module}, {sess.decode_module}")


def _without_metadata(text: str) -> list[str]:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def kernel(m):
        raw = base64.b64decode(m.group(2) + "=" * (-len(m.group(2)) % 4))
        with ctx:
            asm = ir.Module.parse(raw).operation.get_asm(
                enable_debug_info=False)
        return m.group(1) + asm.replace("\n", " ")

    text = BODY.sub(kernel, re.sub(r", metadata=\{[^}]*\}", "", text))
    out, table = [], False
    for line in text.split("\n")[1:]:
        table = line in TABLES or (table and line != "")
        if not table:
            out.append(line)
    return out


def _read(path: Path) -> str:
    with gzip.open(path, "rt") as f:
        return f.read()


def diff(a: Path, b: Path) -> int:
    la, lb = (_without_metadata(_read(p)) for p in (a, b))
    lines = list(difflib.unified_diff(la, lb, str(a), str(b), lineterm="",
                                      n=0))
    print(f"{a} vs {b}: {len(la)} and {len(lb)} lines, "
          f"{len(lines)} lines of diff")
    print("\n".join(line[:300] for line in lines[:40]))
    return 1 if lines else 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["dump"] and len(sys.argv) > 3:
        dump(Path(sys.argv[2]), sys.argv[3:])
    elif sys.argv[1:2] == ["diff"] and len(sys.argv) == 4:
        sys.exit(diff(Path(sys.argv[2]), Path(sys.argv[3])))
    else:
        sys.exit(__doc__)
