"""Bidirectional name <-> UID dictionaries.

Reference behavior: /root/reference/src/uid/UniqueId.java (:62) — three
dictionaries (metrics, tagk, tagv) mapping strings to fixed-width byte UIDs
with atomic assignment, prefix `suggest` (max 25, :89), `rename` (:1095) and
`delete` (:1212).  The reference persists these in the `tsdb-uid` HBase table;
here the dictionary is an in-process store with optional snapshot persistence
handled by the storage layer.  Random-UID mode mirrors RandomUniqueId.java.
"""

from __future__ import annotations

import random
import re
import threading
from enum import Enum
from typing import Iterable


class UniqueIdType(Enum):
    METRIC = "metric"
    TAGK = "tagk"
    TAGV = "tagv"

    @staticmethod
    def from_string(value: str) -> "UniqueIdType":
        v = value.lower()
        for t in UniqueIdType:
            if t.value == v:
                return t
        raise ValueError("Invalid type: " + value)


class NoSuchUniqueName(LookupError):
    def __init__(self, kind: str, name: str):
        super().__init__("No such name for '%s': '%s'" % (kind, name))
        self.kind = kind
        self.name = name


class NoSuchUniqueId(LookupError):
    def __init__(self, kind: str, uid: bytes):
        super().__init__("No such unique ID for '%s': %s" % (kind, uid.hex()))
        self.kind = kind
        self.uid = uid


class FailedToAssignUniqueIdException(RuntimeError):
    pass


MAX_SUGGESTIONS = 25  # UniqueId.java:89

_VALID_NAME = re.compile(r"^[-_./a-zA-Z0-9À-ヿ]+$")


def validate_uid_name(what: str, name: str) -> None:
    """Charset check mirroring Tags.validateString (Tags.java) used at assignment."""
    if name is None:
        raise ValueError("Invalid %s: null" % what)
    if not _VALID_NAME.match(name):
        raise ValueError(
            "Invalid %s (\"%s\"): illegal character" % (what, name))


class UniqueId:
    """One name<->UID dictionary of a given kind and byte width."""

    def __init__(self, kind: UniqueIdType, width: int = 3,
                 random_ids: bool = False):
        if width <= 0 or width > 8:
            raise ValueError("Invalid width: %d" % width)
        self.kind = kind
        self.width = width
        self.random_ids = random_ids
        self._lock = threading.RLock()
        # guarded-by: _lock
        self._name_to_id: dict[str, int] = {}
        self._id_to_name: dict[int, str] = {}  # guarded-by: _lock
        # MAXID counter row equivalent (UniqueId.java:79)  # guarded-by: _lock
        self._max_id = 0
        self.cache_hits = 0  # guarded-by: _lock
        self.cache_misses = 0  # guarded-by: _lock
        self.assigned = 0  # guarded-by: _lock
        self._id_filter = None  # UniqueIdFilterPlugin hook  # guarded-by: _lock
        # bumped by rename/delete: what a name resolved to before may no
        # longer hold (the planner's memoised series resolution reads it)
        self.renames = 0  # guarded-by: _lock
        self.on_create = None   # callable(name, uid) on new assignment

    @property
    def max_possible_id(self) -> int:
        return (1 << (8 * self.width)) - 1

    def set_filter(self, plugin) -> None:
        with self._lock:
            self._id_filter = plugin

    # -- lookups --

    def get_id(self, name: str) -> int:
        """Name -> UID, raising NoSuchUniqueName (UniqueId.getId)."""
        # counters bump inside the same hold as the lookup: the lockless
        # form lost increments under concurrent resolution (tsdblint
        # lock-unguarded-mutation)
        with self._lock:
            uid = self._name_to_id.get(name)
            if uid is None:
                self.cache_misses += 1
            else:
                self.cache_hits += 1
        if uid is None:
            raise NoSuchUniqueName(self.kind.value, name)
        return uid

    def get_name(self, uid: int) -> str:
        """UID -> name, raising NoSuchUniqueId (UniqueId.getName)."""
        with self._lock:
            name = self._id_to_name.get(uid)
        if name is None:
            raise NoSuchUniqueId(self.kind.value, self.uid_to_bytes(uid))
        return name

    def has_name(self, name: str) -> bool:
        with self._lock:
            return name in self._name_to_id

    def get_or_create_id(self, name: str) -> int:
        """Assign a new UID if missing (UniqueId.getOrCreateIdAsync :865)."""
        with self._lock:
            uid = self._name_to_id.get(name)
            if uid is not None:
                self.cache_hits += 1
                return uid
            validate_uid_name(self.kind.value, name)
            if self._id_filter is not None and not self._id_filter.allow_uid_assignment(
                    name, self.kind):
                raise FailedToAssignUniqueIdException(
                    "UID assignment denied by filter for " + name)
            if self.random_ids:
                # RandomUniqueId.java: random assignment with retry on collision.
                for _ in range(10):
                    candidate = random.randint(1, self.max_possible_id)
                    if candidate not in self._id_to_name:
                        uid = candidate
                        break
                else:
                    raise FailedToAssignUniqueIdException(
                        "Failed to find a free random UID for " + name)
            else:
                if self._max_id >= self.max_possible_id:
                    raise FailedToAssignUniqueIdException(
                        "All Unique IDs for %s on %d bytes are already assigned!"
                        % (self.kind.value, self.width))
                self._max_id += 1
                uid = self._max_id
            self._name_to_id[name] = uid
            self._id_to_name[uid] = name
            self.assigned += 1
        # Outside the lock: realtime-UID meta hook (UniqueIdAllocator's
        # UIDMeta.storeNew callback under tsd.core.meta.enable_realtime_uid).
        if self.on_create is not None:
            self.on_create(name, uid)
        return uid

    # -- admin (UniqueId.suggest :971, rename :1095, deleteAsync :1212) --

    def suggest(self, prefix: str, max_results: int = MAX_SUGGESTIONS) -> list[str]:
        if max_results <= 0:
            max_results = MAX_SUGGESTIONS
        with self._lock:
            names = sorted(n for n in self._name_to_id if n.startswith(prefix))
        return names[:max_results]

    def rename(self, old_name: str, new_name: str) -> None:
        with self._lock:
            if new_name in self._name_to_id:
                raise ValueError(
                    "An UID with name %s for %s already exists"
                    % (new_name, self.kind.value))
            uid = self._name_to_id.pop(old_name, None)
            if uid is None:
                raise NoSuchUniqueName(self.kind.value, old_name)
            validate_uid_name(self.kind.value, new_name)
            self._name_to_id[new_name] = uid
            self._id_to_name[uid] = new_name
            self.renames += 1

    def delete(self, name: str) -> int:
        with self._lock:
            uid = self._name_to_id.pop(name, None)
            if uid is None:
                raise NoSuchUniqueName(self.kind.value, name)
            self._id_to_name.pop(uid, None)
            self.renames += 1
            return uid

    # -- codec helpers --

    def uid_to_bytes(self, uid: int) -> bytes:
        return uid.to_bytes(self.width, "big")

    def bytes_to_uid(self, raw: bytes) -> int:
        return int.from_bytes(raw, "big")

    def uid_to_hex(self, uid: int) -> str:
        return self.uid_to_bytes(uid).hex().upper()

    def hex_to_uid(self, hexstr: str) -> int:
        return int(hexstr, 16)

    # -- introspection --

    def __len__(self) -> int:
        with self._lock:
            return len(self._name_to_id)

    def names(self) -> Iterable[str]:
        with self._lock:
            return list(self._name_to_id)

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._name_to_id)

    def restore(self, mapping: dict[str, int]) -> None:
        with self._lock:
            self._name_to_id = dict(mapping)
            self._id_to_name = {v: k for k, v in self._name_to_id.items()}
            self._max_id = max(self._id_to_name, default=0)
