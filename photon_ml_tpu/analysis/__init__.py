"""photonlint: project-native static analysis for the hot-path invariants.

Every performance and robustness property this repo ships — zero fresh XLA
traces when warm, exactly one batched `jax.device_get` flush per outer
iteration, copy-before-donate aliasing guards, fsync+atomic-replace
checkpoint writes, string-literal fault sites — is an invariant the code
states in prose and the tests hold after the fact.  This package checks
them at diff time, over every file, including paths no test exercises.

    python -m photon_ml_tpu.analysis.lint photon_ml_tpu/

Rules (see `rules.py` for the catalog, README "Static analysis" for docs):

  PH001  host sync in hot-path modules (float()/bool()/.item()/np.asarray/
         jax.device_get on device values outside flush points)
  PH002  retrace hazards inside jit/vmap-wrapped functions
  PH003  reads of a buffer after it was passed in a donated position
  PH004  fault-site discipline (string-literal sites declared in
         utils.faults.SITES, declared context keys only)
  PH005  durability (checkpoint/model-io writes go through
         utils.durable helpers, never bare open(..., "w")/json.dump)
  PH006  nondeterminism (time.*/random.* inside traced regions)

Suppression: `# photonlint: disable=PH001` on the finding's line,
`# photonlint: disable-file=PH001` anywhere in a file,
`# photonlint: flush-point` on a `def` line to whitelist a designated
host-sync flush point (PH001).  Grandfathered findings live in
`analysis/baseline.json` (`--write-baseline` regenerates it).
"""
from photon_ml_tpu.analysis.engine import Finding, lint_paths  # noqa: F401
