"""Source-level contract lints (AST rules) + the config-registry audit.

These encode the repo's kernel, sharding and precision policies as
mechanical rules over ``src/repro`` (ROADMAP.md §Durable design
contracts, DESIGN.md §7):

  * **pallas-compiler-params** — every ``pl.pallas_call`` must pass
    ``compiler_params=_compiler_params(...)``: the one place the VMEM
    limit the kernels compile under is set.
  * **compat-shard-map** — ``jax.shard_map`` may only be used (or any
    shard_map imported) inside ``distributed/sharding.py``, whose
    ``compat_shard_map`` is the one call site (replication checking off).
  * **no-raw-fft** — ``jnp.fft`` is the oracle's tool (``kernels/ref.py``)
    and the data generator's (``data/pde.py``); production paths must use
    the truncated-DFT formulation (``core/spectral.py`` operands through
    the kernels), where truncation is free and fusion is possible.
  * **dtype-literal** — inside the precision-policy-governed files, float
    dtype literals (``jnp.float32`` & co) may appear only at the
    allowlisted cast-ownership boundaries (DESIGN.md §4); everywhere else
    the dtype must come from the ``PrecisionPolicy``. Annotate a
    legitimate new boundary with ``# lint: allow-dtype`` (and say why in
    DESIGN.md §4).

``check_config_registry`` closes the configs audit: every seeded arch
must be enumerated by ``configs.runnable_cells()`` with, per cell, either
runnability or a non-empty skip reason — and at least one runnable cell.

This module imports no jax: it runs anywhere, first, fast.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.analysis import Finding

# Where each policy legitimately lives (paths relative to src/repro).
SHARD_MAP_HOME = "distributed/sharding.py"
FFT_ALLOWED = ("kernels/ref.py", "data/pde.py")

# Files under the PrecisionPolicy contract, with the owner functions
# allowed to hold float-dtype literals ("<module>" = module level). These
# are exactly the cast-ownership boundaries of DESIGN.md §4.
DTYPE_SCOPE: Dict[str, Tuple[str, ...]] = {
    "kernels/engine.py": ("_bf16_pieces",      # end_mlp: bf16 pieces of
                          "_make_end_mlp_kernel",  # an f32 operand, f32
                          "end_mlp_call"),     # accumulation (§4)
    "kernels/cgemm.py": ("<module>",),         # _F32 accumulator default
    "kernels/dft.py": ("<module>",),           # _F32 accumulator default
    "kernels/ops.py": ("_spectral_oracle",     # f32 oracle boundary
                       "_block_tail",          # f32 epilogue accumulation
                       "end_mlp_terms"),       # f32 operands split in 1-6
    "core/fno.py": ("_dense_init",             # f32 master-param init
                    "relative_l2"),            # f32 metric reduction
    "core/spectral_conv.py": ("init_spectral_nd",),
    "train/train_step.py": ("make_train_step",  # f32 grad-acc fallback
                            "train_step"),      # f32 loss accumulator
}
DTYPE_ATTRS = frozenset({"float32", "float64", "float16", "bfloat16"})
DTYPE_PRAGMA = "lint: allow-dtype"


def repo_src_root() -> Path:
    return Path(__file__).resolve().parents[1]


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


class _Visitor(ast.NodeVisitor):
    def __init__(self, rel: str, lines: Sequence[str]):
        self.rel = rel
        self.lines = lines
        self.owners = ["<module>"]
        self.findings: List[Finding] = []

    # -- owner tracking ------------------------------------------------
    def visit_FunctionDef(self, node):
        self.owners.append(node.name)
        self.generic_visit(node)
        self.owners.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _where(self, node) -> str:
        return f"{self.rel}:{node.lineno}"

    def _line_has_pragma(self, node) -> bool:
        line = self.lines[node.lineno - 1] if node.lineno <= len(
            self.lines) else ""
        return DTYPE_PRAGMA in line

    # -- rules ---------------------------------------------------------
    def visit_Call(self, node: ast.Call):
        if _call_name(node) == "pallas_call":
            cp = next((kw.value for kw in node.keywords
                       if kw.arg == "compiler_params"), None)
            ok = (isinstance(cp, ast.Call)
                  and _call_name(cp).endswith("_compiler_params"))
            if not ok:
                self.findings.append(Finding(
                    "pallas-compiler-params", self._where(node),
                    "pl.pallas_call without compiler_params="
                    "_compiler_params(...) — every kernel compiles under "
                    "the one VMEM limit kernels/__init__ sets"))
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        mod = node.module or ""
        names = [a.name for a in node.names]
        if ("shard_map" in mod or "shard_map" in names) \
                and self.rel != SHARD_MAP_HOME:
            self.findings.append(Finding(
                "compat-shard-map", self._where(node),
                "raw shard_map import — use distributed.sharding."
                "compat_shard_map, the repo's one shard_map call site"))
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import):
        if any("shard_map" in a.name for a in node.names) \
                and self.rel != SHARD_MAP_HOME:
            self.findings.append(Finding(
                "compat-shard-map", self._where(node),
                "raw shard_map import — use distributed.sharding."
                "compat_shard_map"))
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr == "shard_map" and self.rel != SHARD_MAP_HOME:
            self.findings.append(Finding(
                "compat-shard-map", self._where(node),
                "raw jax.shard_map — use distributed.sharding."
                "compat_shard_map, the repo's one shard_map call site"))
        if node.attr == "fft" and self.rel not in FFT_ALLOWED:
            self.findings.append(Finding(
                "no-raw-fft", self._where(node),
                "jnp.fft on a production path — the kernels consume the "
                "truncated-DFT operand formulation (core/spectral.py); "
                "jnp.fft belongs only to the oracle (kernels/ref.py) and "
                "the data generators (data/pde.py)"))
        if (node.attr in DTYPE_ATTRS
                and isinstance(node.value, ast.Name)
                and node.value.id in ("jnp", "np", "numpy")
                and self.rel in DTYPE_SCOPE
                and self.owners[-1] not in DTYPE_SCOPE[self.rel]
                and not self._line_has_pragma(node)):
            self.findings.append(Finding(
                "dtype-literal", self._where(node),
                f"dtype literal {node.value.id}.{node.attr} outside the "
                f"allowlisted cast-ownership boundaries of {self.rel} "
                f"(owner {self.owners[-1]!r}) — take the dtype from the "
                f"PrecisionPolicy, or annotate a legitimate new boundary "
                f"with '# {DTYPE_PRAGMA}' and document it in DESIGN.md §4"))
        self.generic_visit(node)


def lint_file(path: Path, root: Optional[Path] = None) -> List[Finding]:
    root = root or repo_src_root()
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [Finding("ast-parse", f"{rel}:{e.lineno}",
                        f"file does not parse: {e.msg}")]
    v = _Visitor(rel, src.splitlines())
    v.visit(tree)
    return v.findings


def run_ast_lints(root: Optional[Path] = None,
                  files: Optional[Iterable[Path]] = None) -> List[Finding]:
    """Lint every .py file under `root` (default: src/repro)."""
    root = root or repo_src_root()
    if files is None:
        files = sorted(p for p in root.rglob("*.py")
                       if "__pycache__" not in p.parts)
    findings: List[Finding] = []
    for path in files:
        findings += lint_file(path, root)
    return findings


def check_config_registry() -> List[Finding]:
    """Every seeded arch builds at least one runnable cell, and every
    skipped cell carries a non-empty reason (the carried-forward
    configs.skip_reason audit)."""
    from repro.configs import ALL_IDS, runnable_cells

    findings: List[Finding] = []
    cells = list(runnable_cells())
    by_arch: Dict[str, List] = {}
    for arch, shape, reason in cells:
        by_arch.setdefault(arch, []).append((shape, reason))
        if reason is not None and not str(reason).strip():
            findings.append(Finding(
                "config-registry", f"{arch}/{shape}",
                "cell is skipped with an EMPTY reason — state why or make "
                "it runnable"))
    for arch in ALL_IDS:
        rows = by_arch.get(arch)
        if not rows:
            findings.append(Finding(
                "config-registry", arch,
                "seeded arch is never enumerated by "
                "configs.runnable_cells() — it can silently rot; add it "
                "to the cell grid or remove the config"))
        elif not any(reason is None for _, reason in rows):
            findings.append(Finding(
                "config-registry", arch,
                "arch has no runnable cell at all (every shape skipped) — "
                "a config nothing can ever run is dead weight"))
    return findings
