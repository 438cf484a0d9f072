"""Reference semantics of version-tagged download billing.

``VersionCache`` is the per-client dict the vectorized
``ClientStateMatrix.bill_downloads`` (``core/client_state.py``) is
parity-tested against: one download billed per (client, version)."""

from typing import Any, Dict


class VersionCache:
    """Version-tagged download accounting for the async broadcast.

    The asynchronous engine lets a chunk train on a stale server version;
    a client that already holds that version (it downloaded it in an
    earlier round) must not be billed a second download.  This ledger
    tracks which version tag each client last fetched:

    * ``bill(client_id, tag, nbytes)`` — returns ``nbytes`` and records
      the fetch if the client's cached tag differs, else returns 0;
    * ``holds(client_id, tag)`` — query without billing.

    Tags are opaque hashables.  ``hits`` / ``misses`` count ``bill``
    outcomes since construction — a hit is a reused stale broadcast.
    """

    def __init__(self):
        self._held: Dict[Any, Any] = {}
        self.hits = 0
        self.misses = 0

    def holds(self, client_id, tag) -> bool:
        """True when ``client_id`` already fetched version ``tag``."""
        return self._held.get(client_id) == tag

    def bill(self, client_id, tag, nbytes: int) -> int:
        """Bytes this client's download of version ``tag`` costs now:
        ``nbytes`` on a cache miss (recorded), 0 on a hit."""
        if self.holds(client_id, tag):
            self.hits += 1
            return 0
        self.misses += 1
        self._held[client_id] = tag
        return int(nbytes)
