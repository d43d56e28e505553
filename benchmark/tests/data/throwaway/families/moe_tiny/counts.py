"""Bytes of the throwaway family. Its cache is the `llama` family's (the
same attention). The counts of operations that a chip run's readers would
ask for are left out: a rehearsal on the CPU reads none of them."""

from families.llama.counts import cache_bytes  # noqa: F401
