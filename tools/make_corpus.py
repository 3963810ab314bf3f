#!/usr/bin/env python
"""Vendor a small license-clean REAL-TEXT corpus for the e2e examples.

An early review's top gap: every end-to-end example trained on synthetic
random tokens, so the loss-curve gates never saw real language. This
script assembles a few hundred KB of genuine English prose from the
RUNNING interpreter's standard-library documentation strings — text
written by humans, shipped under the PSF-2.0 license (redistributable
with attribution), and available offline in any Python install, so the
corpus can be regenerated without network egress.

Output: ``examples/data/corpus.txt`` (UTF-8; byte-level tokenization is
the intended consumption — see ``examples/gpt2/train.py --data *.txt``).
The vendored copy is checked in so tests are deterministic across
Python versions; re-running this script on a different interpreter
produces a different (equally valid) corpus.
"""
from __future__ import annotations

import importlib
import inspect
import io
import os
import re
import sys

# Prose-heavy stdlib modules: tutorial-grade docstrings, not symbol
# soup. Order is deterministic.
MODULES = [
    "argparse", "asyncio", "base64", "bisect", "calendar", "codecs",
    "collections", "concurrent.futures", "configparser", "contextlib",
    "copy", "csv", "datetime", "decimal", "difflib", "dis", "doctest",
    "email", "enum", "fileinput", "fractions", "functools", "gettext",
    "glob", "gzip", "hashlib", "heapq", "hmac", "html", "http.client",
    "imaplib", "inspect", "ipaddress", "itertools", "json", "locale",
    "logging", "lzma", "mailbox", "math", "mimetypes", "multiprocessing",
    "netrc", "nntplib", "numbers", "os", "pathlib", "pdb", "pickle",
    "pickletools", "pkgutil", "platform", "plistlib", "poplib", "pprint",
    "profile", "pstats", "queue", "random", "re", "sched", "secrets",
    "selectors", "shelve", "shlex", "shutil", "signal", "smtplib",
    "socket", "socketserver", "sqlite3", "ssl", "statistics", "string",
    "struct", "subprocess", "tarfile", "tempfile", "textwrap",
    "threading", "timeit", "tokenize", "trace", "traceback", "turtle",
    "types", "typing", "unittest", "urllib.parse", "urllib.request",
    "uuid", "warnings", "wave", "weakref", "webbrowser", "xml.dom",
    "xml.etree.ElementTree", "zipfile", "zlib",
]

TARGET_BYTES = 400_000


def _clean(doc: str) -> str:
    doc = inspect.cleandoc(doc)
    # Strip doctest blocks and signature-only lines: keep prose.
    lines = [l for l in doc.splitlines()
             if not l.lstrip().startswith((">>>", "..."))]
    text = "\n".join(lines).strip()
    text = re.sub(r"\n{3,}", "\n\n", text)
    return text


def collect(target: int = TARGET_BYTES) -> str:
    out = io.StringIO()
    seen = set()
    for name in MODULES:
        try:
            mod = importlib.import_module(name)
        except Exception:
            continue
        docs = []
        if mod.__doc__:
            docs.append(mod.__doc__)
        for _, obj in sorted(vars(mod).items()):
            if not (inspect.isclass(obj) or inspect.isfunction(obj)):
                continue
            if getattr(obj, "__module__", None) != mod.__name__:
                continue
            d = inspect.getdoc(obj)
            if d and len(d) > 120:
                docs.append(d)
        for d in docs:
            t = _clean(d)
            if len(t) < 80 or t in seen:
                continue
            seen.add(t)
            out.write(t)
            out.write("\n\n")
        if out.tell() >= target:
            break
    return out.getvalue()[:target]


def main() -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_dir = os.path.join(repo, "examples", "data")
    os.makedirs(out_dir, exist_ok=True)
    text = collect()
    path = os.path.join(out_dir, "corpus.txt")
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)
    print(f"wrote {path}: {len(text.encode('utf-8'))} bytes "
          f"(python {sys.version.split()[0]} stdlib docstrings, PSF-2.0)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
