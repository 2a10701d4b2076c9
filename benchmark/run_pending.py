"""Run a cell that waits in ``benchmark/pending/``.

    python benchmark/run_pending.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The arguments are ``run.py``'s. A cell in ``pending/`` has its driver, traffic
file and readers in place and only its ``BENCHMARK.json`` entries held back
(each file's ``note`` says why). This makes a checkout under
``.bench_out/pending_root`` whose ``BENCHMARK.json`` has every pending file
merged in and whose ``benchmark/``, ``config/`` and program are links to this
one, and hands the process over to that checkout's ``run.py``: the harness
finds its root from its own location. Outputs land under that root's
``.bench_out``. ``rehearse_compile.py`` of that root works the same way.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIRNAME = os.path.basename(os.path.dirname(os.path.abspath(__file__)))
LISTS = ("configs", "workloads", "end_to_end", "per_layer")
LINKED = (BENCH_DIRNAME, "config", "distribuuuu_tpu")


def merged_benchmark(repo: str = REPO) -> dict:
    """``BENCHMARK.json`` as it stands once every pending file has landed:
    new entries at the end of their lists; an entry whose name is there
    already appends its ``workloads`` to that entry's."""
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    pending = os.path.join(repo, BENCH_DIRNAME, "pending")
    for name in sorted(os.listdir(pending)):
        with open(os.path.join(pending, name)) as f:
            entries = json.load(f)
        for key in LISTS:
            have = {e["name"]: e for e in benchmark[key]}
            for entry in entries.get(key, []):
                if entry["name"] in have:
                    have[entry["name"]]["workloads"] += entry["workloads"]
                else:
                    benchmark[key].append(entry)
    return benchmark


def make_root(root: str, repo: str = REPO) -> str:
    """The checkout described above at ``root`` (made anew each call)."""
    os.makedirs(root, exist_ok=True)
    for name in LINKED:
        link = os.path.join(root, name)
        if os.path.lexists(link):
            os.remove(link)
        os.symlink(os.path.join(repo, name), link)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(merged_benchmark(repo), f, indent=1)
    return root


if __name__ == "__main__":
    root = make_root(os.path.join(REPO, ".bench_out", "pending_root"))
    script = os.path.join(root, BENCH_DIRNAME, "run.py")
    os.chdir(root)
    os.execv(sys.executable, [sys.executable, script, *sys.argv[1:]])
