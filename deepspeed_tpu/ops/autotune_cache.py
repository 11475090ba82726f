"""Persistent on-disk autotune cache for per-shape kernel decisions.

One JSON table maps a *shape signature* (leg + shape + dtype + mask flags +
device kind, see ``kernel_dispatch.signature``) to the kernel implementation
and (block_q, block_k) that measured fastest for it.  The offline sweep tool
(``tests/perf/run_attn_sweep.py`` / ``bin/ds_kernel_tune``) is the writer;
``kernel_dispatch.resolve`` is the reader.  When no measurement exists for a
signature the dispatcher falls back to its built-in heuristic table — the
cache only ever *upgrades* a decision, never blocks one.

File format (version-stamped so a schema change can invalidate old tables)::

    {"version": 1,
     "entries": {"<signature>": {"impl": "xla|pallas|folded",
                                 "block_q": 512, "block_k": 1024,
                                 "ms": 42.7, "utc": "...", "note": "..."}}}

Durability follows the checkpoint layer's commit idiom (tmp + fsync +
rename): a writer killed mid-commit leaves either the old table or the new
one, never truncated JSON.  A corrupt/unreadable table degrades to "no
measurements" — dispatch still works off the heuristics.

Location: the tracked table beside this module
(``deepspeed_tpu/ops/attn_dispatch.json``, empty until a chip sweep commits
to it), so which kernel compiles follows from the checkout and from nothing
in a user's home.  ``$DS_TPU_ATTN_CACHE_DIR``/attn_dispatch.json replaces it
for the sweep tool and for tests (tier-1 points it at a hermetic temp dir).
"""

import json
import os
import time
from typing import Dict, Optional

CACHE_VERSION = 1
CACHE_FILENAME = "attn_dispatch.json"


def cache_dir() -> str:
    """Directory holding the dispatch table — ``$DS_TPU_ATTN_CACHE_DIR`` if
    set, else this module's own directory (the tracked table)."""
    return (os.environ.get("DS_TPU_ATTN_CACHE_DIR")
            or os.path.dirname(os.path.abspath(__file__)))


def cache_path() -> str:
    return os.path.join(cache_dir(), CACHE_FILENAME)


def _load_table(path: str) -> Dict:
    """Parse the table at ``path``; any failure (missing, torn, wrong
    version) reads as an empty table — measurements are an optimization,
    never a dependency."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(doc, dict) or doc.get("version") != CACHE_VERSION:
        return {}
    entries = doc.get("entries")
    return entries if isinstance(entries, dict) else {}


class AutotuneCache:
    """mtime-validated view over the on-disk table plus the commit writer."""

    def __init__(self, path: Optional[str] = None):
        self._explicit_path = path
        self._loaded_for = None  # (path, mtime) the in-memory table mirrors
        self._entries: Dict[str, Dict] = {}

    @property
    def path(self) -> str:
        return self._explicit_path or cache_path()

    def _refresh(self) -> None:
        path = self.path
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            mtime = None
        key = (path, mtime)
        if key == self._loaded_for:
            return
        self._entries = _load_table(path) if mtime is not None else {}
        self._loaded_for = key

    def lookup(self, signature: str) -> Optional[Dict]:
        self._refresh()
        ent = self._entries.get(signature)
        return dict(ent) if isinstance(ent, dict) else None

    def entries(self) -> Dict[str, Dict]:
        self._refresh()
        return dict(self._entries)

    def commit(self, signature: str, entry: Dict) -> None:
        """Merge one measured winner into the table and atomically replace
        it (tmp/fsync/rename — same crash-consistency contract as the
        checkpoint layer's manifest writer)."""
        path = self.path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        entries = _load_table(path)
        entries[signature] = dict(entry,
                                  utc=time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                    time.gmtime()))
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                      indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._loaded_for = None  # next lookup re-reads the committed table

    def source_description(self) -> str:
        """Human line for ds_report: where decisions come from right now."""
        self._refresh()
        if self._entries:
            return f"measured ({self.path}, {len(self._entries)} entries)"
        return f"heuristic (no measured table at {self.path})"


_default_cache: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    """Process-wide cache view.  The path is re-resolved inside ``_refresh``
    on every lookup, so a test that monkeypatches ``DS_TPU_ATTN_CACHE_DIR``
    gets its hermetic table without touching module state."""
    global _default_cache
    if _default_cache is None:
        _default_cache = AutotuneCache()
    return _default_cache
