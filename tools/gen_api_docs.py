"""Generate the API reference (docs/api/*.md) from live docstrings.

The reference ships an mkdocs + mkdocstrings setup (reference mkdocs.yml
+ docs/api/** stubs); this is the equivalent for an offline environment:
one markdown page per package section — module docstring, then every
public class (with method signatures + first docstring paragraph) and
function — generated from the imported modules so it can never drift
silently from the code.  Re-run after API changes:

    JAX_PLATFORMS=cpu python -m tools.gen_api_docs
"""

from __future__ import annotations

import importlib
import inspect
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

OUT_DIR = Path(__file__).resolve().parent.parent / "docs" / "api"

SECTIONS: dict[str, list[str]] = {
    "provider": [
        "quantum_resistant_p2p_tpu.provider.base",
        "quantum_resistant_p2p_tpu.provider.registry",
        "quantum_resistant_p2p_tpu.provider.kem_providers",
        "quantum_resistant_p2p_tpu.provider.sig_providers",
        "quantum_resistant_p2p_tpu.provider.symmetric",
        "quantum_resistant_p2p_tpu.provider.aead_device",
        "quantum_resistant_p2p_tpu.provider.batched",
        "quantum_resistant_p2p_tpu.provider.scheduler",
        "quantum_resistant_p2p_tpu.provider.autotune",
        "quantum_resistant_p2p_tpu.provider.opcache",
        "quantum_resistant_p2p_tpu.provider.health",
        "quantum_resistant_p2p_tpu.faults.plan",
    ],
    "kem": [
        "quantum_resistant_p2p_tpu.kem.mlkem",
        "quantum_resistant_p2p_tpu.kem.frodo",
        "quantum_resistant_p2p_tpu.kem.hqc",
    ],
    "sig": [
        "quantum_resistant_p2p_tpu.sig.mldsa",
        "quantum_resistant_p2p_tpu.sig.sphincs",
    ],
    "core": [
        "quantum_resistant_p2p_tpu.core.keccak",
        "quantum_resistant_p2p_tpu.core.chacha_pallas",
        "quantum_resistant_p2p_tpu.core.sha256",
        "quantum_resistant_p2p_tpu.core.sha512",
        "quantum_resistant_p2p_tpu.core.aes",
        "quantum_resistant_p2p_tpu.core.aes_bitsliced",
        "quantum_resistant_p2p_tpu.core.sortnet",
        "quantum_resistant_p2p_tpu.pyref.chacha_ref",
    ],
    "app-net-storage": [
        "quantum_resistant_p2p_tpu.app.messaging",
        "quantum_resistant_p2p_tpu.app.resumption",
        "quantum_resistant_p2p_tpu.app.message_store",
        "quantum_resistant_p2p_tpu.net.p2p_node",
        "quantum_resistant_p2p_tpu.net.discovery",
        "quantum_resistant_p2p_tpu.net.identity",
        "quantum_resistant_p2p_tpu.storage.key_storage",
        "quantum_resistant_p2p_tpu.storage.secure_logger",
        "quantum_resistant_p2p_tpu.storage.secure_file",
    ],
    "runtime": [
        "quantum_resistant_p2p_tpu.cli",
        "quantum_resistant_p2p_tpu.tui",
        "quantum_resistant_p2p_tpu.config",
        "quantum_resistant_p2p_tpu.parallel.mesh",
        "quantum_resistant_p2p_tpu.utils.benchmarking",
        "quantum_resistant_p2p_tpu.utils.compile_cache",
        "quantum_resistant_p2p_tpu.utils.ctr_drbg",
    ],
    "obs": [
        "quantum_resistant_p2p_tpu.obs.trace",
        "quantum_resistant_p2p_tpu.obs.metrics",
        "quantum_resistant_p2p_tpu.obs.slo",
        "quantum_resistant_p2p_tpu.obs.cost",
        "quantum_resistant_p2p_tpu.obs.http",
        "quantum_resistant_p2p_tpu.obs.flight",
    ],
    "analysis": [
        "tools.analysis.engine",
        "tools.analysis.flow",
        "tools.analysis.flow.callgraph",
        "tools.analysis.flow.taint",
        "tools.analysis.flow.domains",
        "tools.analysis.flow.packs",
        "tools.analysis.flow.sarif",
        "tools.analysis.kernel",
        "tools.analysis.kernel.absdom",
        "tools.analysis.kernel.interp",
        "tools.analysis.kernel.models",
        "tools.analysis.kernel.shapes",
        "tools.analysis.kernel.pallas_checks",
        "tools.analysis.kernel.dataflow",
        "tools.analysis.kernel.packs",
        "tools.analysis.proto",
        "tools.analysis.proto.model",
        "tools.analysis.proto.packs",
        "tools.analysis.life",
        "tools.analysis.life.locks",
        "tools.analysis.life.resources",
        "tools.analysis.life.wipes",
        "tools.analysis.life.packs",
        "tools.analysis.all",
    ],
}


def _first_para(doc: str | None) -> str:
    if not doc:
        return ""
    return inspect.cleandoc(doc).split("\n\n")[0]


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _public_members(mod):
    for name, obj in sorted(vars(mod).items()):
        if name.startswith("_"):
            continue
        if getattr(obj, "__module__", None) != mod.__name__:
            continue  # re-exports documented at their home
        yield name, obj


def render_module(modname: str) -> str:
    mod = importlib.import_module(modname)
    lines = [f"## `{modname}`", ""]
    doc = inspect.cleandoc(mod.__doc__ or "").strip()
    if doc:
        lines += [doc, ""]
    for name, obj in _public_members(mod):
        if inspect.isclass(obj):
            lines += [f"### class `{name}{_sig(obj)}`", ""]
            para = _first_para(obj.__doc__)
            if para:
                lines += [para, ""]
            for mname, meth in sorted(vars(obj).items()):
                if mname.startswith("_") or not callable(meth):
                    continue
                lines.append(f"- `{mname}{_sig(meth)}` — {_first_para(meth.__doc__) or ''}")
            lines.append("")
        elif inspect.isfunction(obj):
            lines += [f"### `{name}{_sig(obj)}`", ""]
            para = _first_para(obj.__doc__)
            if para:
                lines += [para, ""]
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", metavar="SECTION",
                    help="regenerate only these section page(s); other pages "
                         "are left untouched (useful on minimal images where "
                         "some sections' modules cannot import)")
    args = ap.parse_args(argv)
    wanted = set(args.only or SECTIONS)
    unknown = wanted - set(SECTIONS)
    if unknown:
        print(f"unknown section(s): {', '.join(sorted(unknown))}", file=sys.stderr)
        return 2
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    index = ["# API reference", "",
             "Generated from docstrings by `tools/gen_api_docs.py`; regenerate "
             "after API changes.", ""]
    for section, modules in SECTIONS.items():
        if section in wanted:
            page = [f"# {section}", ""]
            for modname in modules:
                page.append(render_module(modname))
                page.append("")
            out = OUT_DIR / f"{section}.md"
            out.write_text("\n".join(page))
            print(f"wrote {out}")
        index.append(f"- [{section}]({section}.md): " + ", ".join(
            f"`{m.split('.')[-1]}`" for m in modules))
    (OUT_DIR / "README.md").write_text("\n".join(index) + "\n")
    print(f"wrote {OUT_DIR / 'README.md'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
