"""The documents name what is in the tree: every file `README.md` and
`docs/*.md` name in backticks exists. A clean-up that deletes a file fails
here until the sentences that pointed at it are gone too."""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

# files a run writes, which a checkout does not hold: name -> who writes it
GENERATED = {
    "run_summary.json": "heturun --telemetry-dir, at the end of a run",
    "roofline.json": "hetuprof --roofline --json, into a telemetry dir",
    "pilot.jsonl": "hetupilot's era ledger under HETU_PILOT_DIR",
    "pilot/pilot.jsonl": "the same, under the telemetry dir",
    "ps_supervisor.jsonl": "PSSupervisor's respawn log",
    "trail-events.jsonl": "hetutrail's straggler events",
    "run_hetu.py": "the reference repository's trainer (docs/MIGRATING.md)",
}
_TOKEN = re.compile(r"`([^`\s]+)`")
_FILE = re.compile(r"\.(py|h|cc|json|jsonl|md)$")


@pytest.fixture(scope="module")
def tree():
    """Every file and directory of the checkout, repo-relative."""
    files = set()
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "__pycache__"
                   and d != "chiprun_out"]
        files.update(os.path.relpath(os.path.join(base, n), ROOT)
                     for n in names + dirs)
    return files


def _named_files(text):
    """Backticked tokens that name one file: `bin/<tool>`, or a path ending
    in a source, JSON or Markdown suffix. Patterns (`metrics-r*.jsonl`,
    `flight-r<N>.json`, `test_hf_{bert,vit}.py`) name no single file."""
    for token in sorted(set(_TOKEN.findall(text))):
        token = token.rstrip(".,;:/").split(":")[0]    # `file.py:123`
        if any(c in token for c in "*<>{}$"):
            continue
        if token.startswith("bin/") or _FILE.search(token):
            yield token


@pytest.mark.parametrize("doc", DOCS)
def test_every_file_a_document_names_exists(doc, tree):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    missing = []
    for token in _named_files(text):
        if token in GENERATED:
            continue
        # the documents shorten paths (`graph/executor.py`, `net.h`): a name
        # stands if it ends the path of some file, component for component
        tail = "/" + token.lstrip("./")
        if not any(("/" + path).endswith(tail) for path in tree):
            missing.append(token)
    assert not missing, f"{doc} names files that do not exist: {missing}"
