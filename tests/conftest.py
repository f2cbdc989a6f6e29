import os

# smoke tests and benches must see the real single CPU device — the 512-way
# override belongs ONLY to launch/dryrun.py.  Tests that need a small mesh
# spawn subprocesses (see test_dryrun_small.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# entry points turn JAX's persistent compile cache on at a fixed path in
# the checkout; tests (and the subprocesses they start) keep it off, so no
# test reads an executable another test wrote
os.environ.setdefault("PSP_NO_COMPILE_CACHE", "1")

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
