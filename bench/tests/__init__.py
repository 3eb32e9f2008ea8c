"""The benchmark's CPU tests (a package, so that its modules never clash
with those of the repository's ``tests/``)."""
