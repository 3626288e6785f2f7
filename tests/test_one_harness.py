"""One way to measure: `benchmarks/run.py` and its harness. `chip_smoke.py`
borrows the harness's helpers and keeps no copy of them, nothing names the
scripts the benchmark replaced, and the commands the documents give can be
run on this tree."""

import ast
import os
import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCS = ("README.md", ".claude/skills/verify/SKILL.md")


def top_level(path: pathlib.Path) -> set:
    """Names of the functions and classes `path` defines at top level
    (what it imports is not a definition)."""
    return {node.name for node in ast.parse(path.read_text()).body
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef))}


def test_chip_smoke_defines_nothing_the_harness_defines():
    harness = [REPO / "benchmarks" / "run.py",
               *sorted((REPO / "benchmarks" / "harness").glob("*.py"))]
    mine = top_level(REPO / "chip_smoke.py") - {"main"}
    copies = {f"{path.relative_to(REPO)}: {name}" for path in harness
              for name in top_level(path) & mine}
    assert not copies, f"import these, do not define them again: {copies}"


SUPERSEDED = re.compile(r"bench_infer|\bbench\.py|RAY_TPU_(INFER_)?BENCH" "_")


def test_nothing_names_the_superseded_benchmarks():
    """The histories (`CHANGES.md`, `PERF.md`, `ROADMAP.md`) may; the
    benchmark's own files are not this repo's to edit."""
    sources = [REPO / "Makefile", *(REPO / doc for doc in DOCS)]
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")
                   and (root, d) != (str(REPO), "benchmarks")]
        sources += [pathlib.Path(root, f) for f in files
                    if f.endswith(".py")]
    sources.remove(pathlib.Path(__file__).resolve())
    found = [f"{path.relative_to(REPO)}:{n}: {line.strip()}"
             for path in sources
             for n, line in enumerate(path.read_text().splitlines(), 1)
             if SUPERSEDED.search(line)]
    assert not found, "\n".join(found)


def smoke_phases() -> set:
    """The `choices` of `chip_smoke.py`'s `--phase`, read from its source."""
    for node in ast.walk(ast.parse((REPO / "chip_smoke.py").read_text())):
        if (isinstance(node, ast.Call) and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "--phase"):
            return set(ast.literal_eval(
                next(k.value for k in node.keywords if k.arg == "choices")))
    raise AssertionError("chip_smoke.py has no --phase argument")


def python_commands(text: str):
    """The arguments of every `python` / `python3` command in a fenced
    block or a code span of a Markdown `text`, as lists of words."""
    fenced = re.findall(r"^```[^\n]*\n(.*?)^```", text, re.S | re.M)
    spans = re.findall(r"`([^`]+)`", re.sub(r"^```.*?^```", "", text,
                                             flags=re.S | re.M))
    lines = [ln for block in fenced
             for ln in block.replace("\\\n", " ").splitlines()]
    for line in lines + [" ".join(span.split()) for span in spans]:
        words = line.split("#")[0].split()
        for i, word in enumerate(words):
            if word in ("python", "python3"):
                yield words[i + 1:]


def test_documented_commands_name_what_exists():
    phases, wrong = smoke_phases(), []
    for doc in DOCS:
        for args in python_commands((REPO / doc).read_text()):
            args = [a.rstrip(".,;)") for a in args]
            script = next((a for a in args if not a.startswith("-")), "")
            if args[:1] == ["-m"] and args[1].startswith(("ray_tpu",
                                                          "benchmarks")):
                module = REPO / args[1].replace(".", "/")
                if not (module.is_dir()
                        or module.with_suffix(".py").exists()):
                    wrong.append(f"{doc}: no module {args[1]}")
            elif script.endswith(".py") and not (REPO / script).exists():
                wrong.append(f"{doc}: no script {script}")
            elif script == "chip_smoke.py" and "--phase" in args:
                phase = args[args.index("--phase") + 1]
                if phase not in phases:
                    wrong.append(f"{doc}: chip_smoke.py has no phase {phase}")
    assert not wrong, wrong


# What a serving cell's `correct` compares, held to the mix in tier-1 too
# (ISSUE 48): the benchmark's own cases, run from here as they stand.
from benchmarks.tests.test_check_sample import (  # noqa: E402,F401
    test_a_chosen_request_short_of_its_tokens_is_a_problem_not_a_resample,
    test_the_generator_draws_what_it_drew_before_it_kept_the_index,
    test_the_plan_is_the_mix_s_alone,
    test_the_run_waits_for_a_compared_request_and_no_longer,
    test_the_sample_is_the_same_whatever_finished,
)
