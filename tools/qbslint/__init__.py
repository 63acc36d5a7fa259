"""qbslint — repo-invariant static analysis for the QbS reproduction.

The paper's exactness guarantee survives only while every layer of this
repo preserves a handful of invariants that plain pytest cannot see
until they are already broken at runtime: all ``shard_map`` is the
vma-checked ``jax.shard_map``, serving time
flows only through the injectable clock (DESIGN.md §8), cache inserts
go only through ``ServingService.cache_put``, and ``StreamingService``
state is ``_lock``-guarded across timer threads.  qbslint turns each of
those conventions into a machine-checked rule over the stdlib ``ast``:

=======  ==============================================================
QBS001   ``jax.experimental.shard_map`` imported/used
QBS002   wall-clock (``time.time``/``monotonic``/``sleep``,
         ``threading.Timer``) in ``serving/`` outside ``clock.py``
QBS003   host-sync calls (``.item()``, ``int()``/``float()`` on
         non-literal args, ``np.asarray``, ``block_until_ready``,
         ``jax.device_get``) inside a jitted function body
QBS004   ``jax.jit(...)`` constructed inside a loop or per-call
         function body (silent recompile churn on the hot path)
QBS005   mutation of a declared guarded field
         (``_QBS_GUARDED_FIELDS``) outside ``with self._lock``
QBS006   ``ResultCache`` writes bypassing ``ServingService.cache_put``
=======  ==============================================================

Run it as ``python -m tools.qbslint src`` (exit 0 = clean).  Suppress a
deliberate violation inline with ``# qbslint: disable=QBS003`` on the
flagged line, or file-wide with ``# qbslint: disable-file=QBS001`` on
any line; a method whose contract is "caller holds the lock" is marked
``# qbslint: locked`` on its ``def`` line (the runtime sanitizer,
``repro.serving.debug``, verifies those markers don't lie).

The rule catalogue with rationale lives in DESIGN.md §9.
"""
from .core import Finding, LintError, lint_file, lint_paths, lint_source
from .rules import ALL_RULES

__all__ = [
    "ALL_RULES",
    "Finding",
    "LintError",
    "lint_file",
    "lint_paths",
    "lint_source",
]
