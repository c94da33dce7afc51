"""Host utilities.  Import submodules explicitly: ``trees`` pulls in jax,
``timing`` does not, and fabric processes must stay jax-free until they
build an engine."""
