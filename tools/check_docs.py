#!/usr/bin/env python
"""Docs/link check (the CI `docs` leg; `make docs-check`).

Verifies the documentation surface stays truthful:

  1. every relative markdown link in README/DESIGN/ROADMAP/CHANGES points at
     an existing file (and an existing heading, for #anchors);
  2. every ``DESIGN.md §N[.M]`` reference in the source tree resolves to a
     section marker actually present in DESIGN.md, and DESIGN.md itself
     still carries every required top-level section marker (§1–§6);
  3. every documented command is runnable at ``--help`` level: the ROADMAP
     tier-1 command plus each ``python ...`` command found in README.md /
     DESIGN.md / ROADMAP.md — inline backticks AND fenced ```…``` blocks
     (module/script resolved, args replaced by ``--help``) — plus the
     explicit entry-point list below;
  4. every long ``--flag`` a documented command passes actually exists in
     that command's ``--help`` output (a doc snippet naming a flag the CLI
     dropped — or never grew — fails here).

Exit code 0 == all good; failures are listed one per line.
"""
from __future__ import annotations

import os
import re
import shlex
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "DESIGN.md", "ROADMAP.md", "CHANGES.md", "PAPER.md"]
COMMAND_DOCS = ["README.md", "DESIGN.md", "ROADMAP.md"]
SOURCE_DIRS = ["src", "benchmarks", "examples", "tests", "tools"]

# top-level DESIGN.md sections that must exist (docstring references point
# into these; §6 is the multi-host sweep surface, §7 the kernel's
# execution point, §8 the phenotype-dedup evaluation cache, §9 the sampled
# evaluation mode, §10 the exact-verification escalation tier, §11 the
# async commit pipeline + island migration, §12 the circuit-artifact
# registry and the evolve → LUT → serve deployment path)
REQUIRED_DESIGN_SECTIONS = ["§1", "§2", "§3", "§4", "§5", "§6", "§7", "§8",
                            "§9", "§10", "§11", "§12"]

# argparse-bearing entry points that must answer --help (quickstart.py is
# deliberately absent: it has no CLI and would run the full search)
ENTRY_POINTS = [
    [sys.executable, "-m", "repro.launch.evolve", "--help"],
    [sys.executable, "-m", "repro.launch.train", "--help"],
    [sys.executable, "-m", "repro.launch.serve", "--help"],
    [sys.executable, "-m", "repro.launch.dryrun", "--help"],
    [sys.executable, "-m", "repro.launch.roofline", "--help"],
    [sys.executable, "-m", "repro.launch.export", "--help"],
    [sys.executable, "-m", "benchmarks.run", "--help"],
    [sys.executable, "-m", "benchmarks.kernel_micro", "--help"],
    [sys.executable, "examples/pareto_sweep.py", "--help"],
    [sys.executable, "examples/approx_nn_inference.py", "--help"],
    [sys.executable, "examples/train_lm.py", "--help"],
    [sys.executable, "tools/check_bench.py", "--help"],
    [sys.executable, "-m", "pytest", "--help"],
]

# flags that must exist in specific --help outputs even when no doc snippet
# happens to pass them
REQUIRED_FLAGS = {
    ("-m", "repro.launch.evolve"): ["--backend", "--dedup",
                                    "--dedup-cache-size", "--eval-mode",
                                    "--sample-size", "--input-dist",
                                    "--certify", "--certify-budget",
                                    "--async-commit", "--migrate-every",
                                    "--migrate-timeout",
                                    "--export-artifacts", "--profile-dir"],
    ("-m", "repro.launch.serve"): ["--approx-lut", "--summary-out"],
    ("-m", "repro.launch.export"): ["--results-dir", "--out", "--top-k",
                                    "--require-certified", "--verify"],
    ("-m", "benchmarks.kernel_micro"): ["--json", "--smoke"],
    ("tools/check_bench.py",): ["--baseline", "--max-regression"],
}

# documented scripts that must NOT be --help-probed (no argparse: running
# them executes the real workload)
SKIP_HELP = {"examples/quickstart.py"}

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_SECREF = re.compile(r"DESIGN\.md\s*§(\d+(?:\.\d+)?)")
_CMD = re.compile(r"`((?:[A-Z_][A-Z0-9_]*=\S*\s+)*(?:PYTHONPATH=\S+\s+)?"
                  r"python[^`]*)`")
_FENCE = re.compile(r"^```")


def _slug(heading: str) -> str:
    """GitHub-style anchor slug."""
    s = heading.strip().lower()
    s = re.sub(r"[^\w\s-]", "", s)
    return re.sub(r"\s+", "-", s)


def check_links() -> list[str]:
    errors = []
    headings = {}
    for doc in DOCS:
        path = os.path.join(ROOT, doc)
        if not os.path.exists(path):
            continue
        with open(path) as f:
            text = f.read()
        headings[doc] = {_slug(m.group(1)) for m in
                         re.finditer(r"^#+\s+(.+)$", text, re.M)}
    for doc in DOCS:
        path = os.path.join(ROOT, doc)
        if not os.path.exists(path):
            continue
        with open(path) as f:
            text = f.read()
        for m in _LINK.finditer(text):
            target = m.group(1)
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            fname, _, anchor = target.partition("#")
            if fname and not os.path.exists(os.path.join(ROOT, fname)):
                errors.append(f"{doc}: broken link -> {target}")
                continue
            if anchor:
                owner = fname or doc
                known = headings.get(owner)
                if known is not None and anchor not in known:
                    errors.append(f"{doc}: broken anchor -> {target}")
    return errors


def check_design_sections() -> list[str]:
    path = os.path.join(ROOT, "DESIGN.md")
    if not os.path.exists(path):
        return ["DESIGN.md missing"]
    with open(path) as f:
        design = f.read()
    errors = [f"DESIGN.md: required section marker {sec} missing"
              for sec in REQUIRED_DESIGN_SECTIONS
              if not re.search(rf"^##\s+{sec}\b", design, re.M)]
    for base in SOURCE_DIRS + ["."]:
        root = os.path.join(ROOT, base)
        for dirpath, _, files in os.walk(root):
            if base == "." and dirpath != root:
                continue  # top level: only the .md files themselves
            for name in files:
                if not name.endswith((".py", ".md")):
                    continue
                fpath = os.path.join(dirpath, name)
                with open(fpath, errors="replace") as f:
                    text = f.read()
                for m in _SECREF.finditer(text):
                    if f"§{m.group(1)}" not in design:
                        rel = os.path.relpath(fpath, ROOT)
                        errors.append(f"{rel}: dangling DESIGN.md "
                                      f"§{m.group(1)} reference")
    return sorted(set(errors))


def _tokens(cmd: str) -> list[str]:
    """Shell-split a documented command: joined continuations, trailing
    ``# comments`` dropped, env assignments stripped."""
    try:
        tokens = shlex.split(cmd.replace("\\\n", " "), comments=True)
    except ValueError:
        return []
    return [t for t in tokens if "=" not in t or not
            re.match(r"^[A-Z_][A-Z0-9_]*=", t)]  # strip env assignments


def _help_variant(tokens: list[str]) -> list[str] | None:
    """Rewrite a documented command into its --help invocation: keep the
    interpreter and the module/script target, drop everything else."""
    if not tokens or not tokens[0].startswith("python"):
        return None
    out = [sys.executable]
    rest = tokens[1:]
    if rest[:1] == ["-m"] and len(rest) > 1:
        out += ["-m", rest[1]]
    else:
        script = next((t for t in rest if t.endswith(".py")), None)
        if script is None or script in SKIP_HELP:
            return None
        out.append(script)
    return out + ["--help"]


def _doc_flags(tokens: list[str]) -> set[str]:
    """The long ``--flag`` options a documented command passes (values and
    bracketed optional spellings like ``[--backend ...]`` excluded)."""
    return {t.split("=")[0] for t in tokens[1:]
            if t.startswith("--") and len(t) > 2}


def _iter_doc_commands(text: str):
    """Yield candidate command strings: inline backticked ``python ...``
    commands plus every ``python``-leading line (backslash continuations
    joined) inside fenced code blocks."""
    for m in _CMD.finditer(text):
        yield m.group(1)
    in_fence, buf = False, ""
    for line in text.splitlines():
        if _FENCE.match(line):
            in_fence = not in_fence
            buf = ""
            continue
        if not in_fence:
            continue
        buf = buf + " " + line.strip() if buf else line.strip()
        if buf.endswith("\\"):
            buf = buf[:-1].strip()
            continue
        if re.match(r"^(?:[A-Z_][A-Z0-9_]*=\S+\s+)*python(\s|$)", buf):
            yield buf
        buf = ""


def check_commands() -> list[str]:
    """Every documented command answers --help, and every long flag it is
    documented with exists in that --help output."""
    cmds: dict[tuple, set[str]] = {tuple(c): set() for c in ENTRY_POINTS}
    for target, flags in REQUIRED_FLAGS.items():
        cmds.setdefault((sys.executable, *target, "--help"),
                        set()).update(flags)
    for doc in COMMAND_DOCS:
        path = os.path.join(ROOT, doc)
        if not os.path.exists(path):
            continue
        with open(path) as f:
            text = f.read()
        for cmd in _iter_doc_commands(text):
            tokens = _tokens(cmd)
            variant = _help_variant(tokens)
            if variant:
                cmds.setdefault(tuple(variant), set()).update(
                    _doc_flags(tokens))
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    errors = []
    for cmd, flags in sorted(cmds.items()):
        proc = subprocess.run(list(cmd), cwd=ROOT, env=env,
                              capture_output=True, timeout=300)
        if proc.returncode != 0:
            tail = proc.stderr.decode(errors="replace").strip()[-200:]
            errors.append(f"--help failed ({proc.returncode}): "
                          f"{' '.join(cmd[1:])}: {tail}")
            continue
        helptext = proc.stdout.decode(errors="replace")
        for flag in sorted(flags - {"--help"}):
            # word boundary: a documented "--pod" must not pass because
            # "--pod-index" exists
            if not re.search(re.escape(flag) + r"(?![\w-])", helptext):
                errors.append(f"documented flag {flag} not in "
                              f"{' '.join(cmd[1:-1])} --help")
    return errors


def main() -> int:
    errors = check_links() + check_design_sections() + check_commands()
    for e in errors:
        print(f"FAIL {e}")
    if not errors:
        print("docs check OK (links, DESIGN sections, --help commands)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
