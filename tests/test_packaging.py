"""Packaging: `pip install .` must provide the reference client's exact
import surface (reference learning_orchestra_client/setup.py:1-22) —
the "change only the cluster IP" compatibility contract. And what ships
beside the code: README and the pages under docs/ cite only files that
are in the tree."""

import fnmatch
import functools
import os
import re
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.integration
def test_pip_install_provides_reference_client_surface(tmp_path):
    target = tmp_path / "site"
    install = subprocess.run(
        [
            sys.executable,
            "-m",
            "pip",
            "install",
            "--quiet",
            "--no-deps",
            "--no-build-isolation",
            "--target",
            str(target),
            _REPO_ROOT,
        ],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert install.returncode == 0, install.stderr

    probe = (
        "from learning_orchestra_client import *\n"
        "Context('127.0.0.1')\n"
        "for cls in (DatabaseApi, Projection, Histogram, Tsne, Pca,"
        " DataTypeHandler, Model):\n"
        "    cls()\n"
        "assert DatabaseApi.DATABASE_API_PORT == '5000'\n"
        "assert Model.MODEL_BUILDER_PORT == '5002'\n"
        "assert callable(Model.predict) and callable(Model.list_models)\n"
        "assert callable(Model.sweep)\n"
        # the fleet lane ships installed: the router-URL probe on the
        # client and the placement/router modules (stdlib imports only
        # at module top — jax/werkzeug load lazily)
        "assert callable(Model._router_base)\n"
        "import learningorchestra_tpu.serve.fleet as fleet\n"
        "assert callable(fleet.validate_env)\n"
        # the coalescing stage + batched-fit entry points ship installed
        "import learningorchestra_tpu.sched.coalesce as co\n"
        "assert callable(co.global_coalescer)\n"
        # the flight recorder ships with the telemetry package (stdlib
        # imports only, so the bare install can load it)
        "import learningorchestra_tpu.telemetry.profile as prof\n"
        "assert callable(prof.chrome_trace)\n"
        "assert callable(prof.sample_stacks)\n"
        # the zero-copy wire (frame v2 + shm ring + dtype policy) ships
        # installed and imports without jax
        "import learningorchestra_tpu.core.shmring as shmring\n"
        "assert callable(shmring.shm_bytes)\n"
        "from learningorchestra_tpu.core.wire import MAGIC_V2\n"
        "from learningorchestra_tpu.utils.dtypepolicy import dtype_policy\n"
        "assert dtype_policy() in ('f32', 'bf16')\n"
        # the event-loop serving core ships installed (stdlib selectors
        # only — the bare install can load it without jax/werkzeug)
        "import learningorchestra_tpu.utils.webloop as webloop\n"
        "assert callable(webloop.validate_env)\n"
        "assert webloop.Waiter and webloop.LoopServer\n"
        "print('client surface ok')\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(target)  # ONLY the installed tree
    run = subprocess.run(
        [sys.executable, "-c", probe],
        capture_output=True,
        text=True,
        env=env,
        cwd=str(tmp_path),  # not the repo: imports must resolve from site
        timeout=120,
    )
    assert run.returncode == 0, run.stderr
    assert "client surface ok" in run.stdout


# --- the documents cite files that exist ---------------------------------

_DOC_PAGES = ["README.md", "deploy/README.md"] + sorted(
    f"docs/{name}"
    for name in os.listdir(os.path.join(_REPO_ROOT, "docs"))
    if name.endswith(".md")
)
# a back-quoted token that names a repository file: a path with one of
# these extensions, optionally `:line`, `:from-to` or `::test` after it
_FILE_TOKEN = re.compile(
    r"`(?:\./)?([\w./-]+\.(?:py|sh|json|jsonl|md|yml|toml))"
    r"(?::\d+(?:-\d+)?|::[\w:\[\]-]+)?`"
)
# files of the REFERENCE (hiperbolt/learningOrchestra) the pages cite for
# parity; they are not in this tree and never were
_REFERENCE_FILES = {
    "docker-compose.yml",
    "learning_orchestra_client/readme.md",
    "microservices/data_type_handler_image/server.py",
    "microservices/database_api_image/server.py",
    "microservices/histogram_image/server.py",
    "microservices/model_builder_image/server.py",
    "microservices/pca_image/server.py",
    "microservices/projection_image/server.py",
    "microservices/tsne_image/server.py",
}


@functools.cache
def _tree_files():
    """Every file of the checkout that git would commit: the walk skips
    `.git` and the directories `.gitignore` names (a driver's checkout
    has no `.git` to ask)."""
    with open(os.path.join(_REPO_ROOT, ".gitignore")) as handle:
        lines = [line.strip() for line in handle]
    ignored = [line.rstrip("/") for line in lines if line.endswith("/")]
    files = set()
    for folder, dirs, names in os.walk(_REPO_ROOT):
        dirs[:] = [
            d
            for d in dirs
            if d != ".git"
            and not any(fnmatch.fnmatch(d, pattern) for pattern in ignored)
        ]
        for name in names:
            path = os.path.relpath(os.path.join(folder, name), _REPO_ROOT)
            files.add(path.replace(os.sep, "/"))
    return files


@pytest.mark.parametrize("page", _DOC_PAGES)
def test_documents_cite_files_that_exist(page):
    files = _tree_files()
    with open(os.path.join(_REPO_ROOT, page)) as handle:
        cited = sorted(set(_FILE_TOKEN.findall(handle.read())))
    stale = [
        token
        for token in cited
        if token not in files
        # the tail of a path: `ml/trees.py`, `stack.py`
        and not any(path.endswith("/" + token) for path in files)
        and token not in _REFERENCE_FILES
    ]
    assert not stale, f"{page} cites files that are not in the tree: {stale}"
