"""Imports point one way: the library never imports the benchmark CLI.

`capital_tpu/bench/` (the per-algorithm drivers, the trace tool, the
measurement harness) is a leaf: only `autotune/`, which measures configs
through the harness, may import it.  Every rule the dense path needs (the
base-case pick, the SUMMA mode and precision defaults, the residual gate
and its test operands, the latency percentiles) lives in the layer that
owns it.  Parsed from source, so a lazy import inside a function body
counts too.
"""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PKG = ROOT / "capital_tpu"

LIBRARY_PACKAGES = ("ops", "parallel", "models", "robust", "serve", "utils",
                    "obs", "lint")


def _module_of(path: pathlib.Path, root: pathlib.Path) -> list[str]:
    """Dotted package parts a relative import in `path` resolves against."""
    parts = list(path.relative_to(root).with_suffix("").parts)
    return parts if path.name == "__init__.py" else parts[:-1]


def _imports(path: pathlib.Path, root: pathlib.Path) -> set[str]:
    """Every module `path` imports, absolute, anywhere in the file."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = _module_of(path, root)
                base = base[: len(base) - (node.level - 1)]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module or ""
            out.add(mod)
            # `from capital_tpu import bench` names the subpackage itself
            out.update(f"{mod}.{a.name}" for a in node.names)
    return out


def _hits(paths, banned: str, root: pathlib.Path = ROOT) -> list[str]:
    return sorted(
        f"{p.relative_to(root)}: {m}"
        for p in paths
        for m in _imports(p, root)
        if m == banned or m.startswith(banned + ".")
    )


@pytest.mark.parametrize("package", LIBRARY_PACKAGES)
def test_library_package_does_not_import_the_bench_cli(package):
    files = sorted((PKG / package).rglob("*.py"))
    assert files, f"capital_tpu/{package} has no modules"
    assert _hits(files, "capital_tpu.bench") == []


def test_chip_smoke_uses_library_modules_only():
    assert _hits([ROOT / "chip_smoke.py"], "capital_tpu.bench") == []


def test_no_native_engine():
    assert not (PKG / "native").exists()
    files = sorted(PKG.rglob("*.py")) + sorted(ROOT.glob("*.py"))
    assert _hits(files, "capital_tpu.native") == []


def test_the_scan_sees_lazy_and_relative_imports(tmp_path):
    """The parser the cases above rely on catches the spellings a layering
    break would use."""
    src = tmp_path / "capital_tpu" / "serve" / "x.py"
    src.parent.mkdir(parents=True)
    src.write_text(
        "def f():\n"
        "    from capital_tpu import bench\n"
        "    from ..bench.harness import timed_loop\n"
        "    import capital_tpu.bench.drivers\n"
    )
    assert _hits([src], "capital_tpu.bench", root=tmp_path) == [
        "capital_tpu/serve/x.py: capital_tpu.bench",
        "capital_tpu/serve/x.py: capital_tpu.bench.drivers",
        "capital_tpu/serve/x.py: capital_tpu.bench.harness",
        "capital_tpu/serve/x.py: capital_tpu.bench.harness.timed_loop",
    ]
