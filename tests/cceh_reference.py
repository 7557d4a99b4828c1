"""A plain CCEH, one key at a time, in Python lists: the reference that
`tests/test_cceh_reference.py` holds the device index (`models/cceh.py`)
to. It imports nothing of the program.

It follows the published description (Nam et al., "Write-Optimized
Dynamic Hashing for Persistent Memory", FAST '19) as the reference server
builds it (`server/CCEH_hybrid.h`, the DRAM variant `server/src/cceh.h`):

- keys hash with murmur3-32 of the 8-byte key (low word first); the top
  `Gmax` bits of the hash index a directory of segments (MSB indexing);
- a segment is `W` windows of `P` slots, and a key probes the one window a
  second, independent hash (seed `WINDOW_SEED`) picks;
- a key already in its window is updated where it sits, else it takes the
  window's lowest free slot;
- a full window splits its segment while the segment's local depth is
  below `Gmax`: the entries whose next hash bit is 1 move to a new segment
  at the same window and slot, both segments deepen by one, and the
  directory entries of the upper half point at the new segment;
- a window that stays full evicts its occupant in the lowest slot and
  returns it (the DRAM CCEH returns the victim: the clean-cache contract).

Departures from the paper, each the program's as well:

- the directory is preallocated at depth `Gmax = log2(initial segments) +
  split_headroom`, each entry replicated over the prefixes it covers, so
  doubling is only a deeper local depth; the paper doubles without bound;
- an insert tries `split_headroom + 2` rounds of place-then-split, then
  places once more and otherwise evicts; the paper splits until it fits;
- eviction replaces the reference's reuse of an invalid (pattern-mismatch)
  slot: splits here clear the moved slots at once.
"""

from __future__ import annotations

M32 = 0xFFFFFFFF
WINDOW_SEED = 0x77AA55EE


def _rotl(x: int, r: int) -> int:
    return ((x << r) | (x >> (32 - r))) & M32


def murmur3_u64(hi: int, lo: int, seed: int = 0) -> int:
    """murmur3-32 of the key `hi << 32 | lo`, its two 4-byte blocks low
    word first."""
    h = seed & M32
    for k in (lo & M32, hi & M32):
        k = (k * 0xCC9E2D51) & M32
        k = _rotl(k, 15)
        k = (k * 0x1B873593) & M32
        h ^= k
        h = _rotl(h, 13)
        h = (h * 5 + 0xE6546B64) & M32
    h ^= 8
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & M32
    return h ^ (h >> 16)


class PlainCCEH:
    """`capacity` slots at start, in segments of `segment_slots`, windows
    of `probe_window` slots, `split_headroom` directory doublings."""

    def __init__(self, capacity: int, segment_slots: int,
                 probe_window: int = 32, split_headroom: int = 1):
        self.P = probe_window
        self.W = max(1, segment_slots // probe_window)
        s0 = max(1, capacity // (self.W * self.P))
        s0 = 1 << (s0 - 1).bit_length()
        g0 = s0.bit_length() - 1
        self.Gmax = max(1, g0 + split_headroom)
        self.rounds = split_headroom + 2
        # segment -> window -> slot -> (key, value) or None
        self.segs = [self._empty() for _ in range(s0)]
        self.ld = [g0] * s0
        self.dir = [i >> (self.Gmax - g0) for i in range(1 << self.Gmax)]

    def _empty(self):
        return [[None] * self.P for _ in range(self.W)]

    def _seg(self, key) -> int:
        return self.dir[murmur3_u64(*key) >> (32 - self.Gmax)]

    def _window(self, key) -> list:
        win = murmur3_u64(*key, seed=WINDOW_SEED) & (self.W - 1)
        return self.segs[self._seg(key)][win]

    def get(self, key):
        for e in self._window(key):
            if e is not None and e[0] == key:
                return e[1]
        return None

    def _place(self, key, value) -> bool:
        window = self._window(key)
        for i, e in enumerate(window):
            if e is not None and e[0] == key:
                window[i] = (key, value)
                return True
        for i, e in enumerate(window):
            if e is None:
                window[i] = (key, value)
                return True
        return False

    def _split(self, s: int) -> int:
        """Split segment `s` if its depth allows; returns segments split."""
        if self.ld[s] >= self.Gmax or len(self.segs) >= 1 << self.Gmax:
            return 0
        d = self.ld[s]
        new = len(self.segs)
        buddy = self._empty()
        for w, window in enumerate(self.segs[s]):
            for i, e in enumerate(window):
                if e is not None and (murmur3_u64(*e[0]) >> (31 - d)) & 1:
                    buddy[w][i], window[i] = e, None
        self.segs.append(buddy)
        self.ld[s] = d + 1
        self.ld.append(d + 1)
        for i, owner in enumerate(self.dir):
            if owner == s and (i >> (self.Gmax - 1 - d)) & 1:
                self.dir[i] = new
        return 1

    def put(self, key, value):
        """Insert or update one key. Returns (evicted (key, value) or None,
        segments split, rounds of place-then-split run)."""
        splits = 0
        for rnd in range(self.rounds):
            if self._place(key, value):
                return None, splits, rnd + 1
            splits += self._split(self._seg(key))
        if self._place(key, value):
            return None, splits, self.rounds
        window = self._window(key)
        victim, window[0] = window[0], (key, value)
        return victim, splits, self.rounds

    def delete(self, key) -> bool:
        window = self._window(key)
        for i, e in enumerate(window):
            if e is not None and e[0] == key:
                window[i] = None
                return True
        return False

    def table(self):
        """(keys, values): one (hi, lo) or None per slot, in the device
        table's order (segment, window, slot), over every segment made."""
        flat = [e for seg in self.segs for window in seg for e in window]
        return ([None if e is None else e[0] for e in flat],
                [None if e is None else e[1] for e in flat])
