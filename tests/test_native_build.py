"""The native runtime's build on first use (``nativert._build``): several
interpreters that ask for it at once against sources with no library all
load it, and nothing half-written or temporary is left beside it."""
import shutil
import subprocess
import sys

import pytest

from deeplearning4j_tpu import nativert

#: loads the loader's module by its path (not the package, whose import
#: would cost each interpreter its seconds of JAX) and asks it for the
#: runtime at ``argv[2]``, built from ``argv[1]``
_ASK = """
import importlib.util, sys
from pathlib import Path
spec = importlib.util.spec_from_file_location("nativert", sys.argv[3])
rt = importlib.util.module_from_spec(spec)
spec.loader.exec_module(rt)
lib = rt._load(Path(sys.argv[1]), Path(sys.argv[2]))
print(lib.dl4j_runtime_version() if lib is not None else rt.load_error())
"""


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++ on this host")
def test_interpreters_that_ask_at_once_all_load_one_build(tmp_path):
    src = tmp_path / "src" / "dl4j_runtime.cpp"
    src.parent.mkdir()
    shutil.copy(nativert._SRC_PATH, src)
    lib = tmp_path / "libdl4j_runtime.so"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _ASK, str(src), str(lib), nativert.__file__],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for _ in range(3)]
    outs = [p.communicate(timeout=300) for p in procs]
    assert [(p.returncode, out.strip()) for p, (out, _) in zip(procs, outs)
            ] == [(0, "4")] * 3, [err[-2000:] for _, err in outs]
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "libdl4j_runtime.so", "libdl4j_runtime.so.lock", "src"]
