"""One tick of kube-apiserver's compactor (k8s.io/apiserver
``storage/etcd3/compact.go``, every ``--etcd-compaction-interval``): the Txn
``If(Version(compact_rev_key) == t) Then(Put(compact_rev_key, rev))
Else(Get(compact_rev_key))`` and, where it succeeded, ``Compact(rev)``, rev
being the revision that the previous tick's Txn returned, ``interval_s``
before. The generator keeps ``t`` and ``rev`` as compact.go does; on the
window's first tick they are 0 and the head revision of the configuration's
history ``interval_s`` before the tick's due time (``State.head_at``).

A mix places it at a fixed second as it places the count polls: an
open-loop stream of its own (``rate`` = 1 / the interval, ``phase``). It is
never sent in the warm-up: the merge-phase rule and the comparison count
from the window's first instant. Its one row (``compact_rev_key``) is
published by the Compact itself, so it is no write of the rule's
(``mergephase.py``).
"""

WRITES = False
DEVICE_READ = False
COMPACTS = True


def issue(gen, op: dict, pool, due: float) -> bool:
    if gen.warming:
        return False
    gen.send_compact(due, op["op"], float(op["interval_s"]))
    return True
