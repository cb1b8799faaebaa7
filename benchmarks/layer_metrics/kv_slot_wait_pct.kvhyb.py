"""Share of the sequences admitted whose admission waited and found the
state slots short when its wait began, in % (program_counter): 100 x
``kv_admit_waits_slots`` / ``kv_seqs_admitted`` (``tpurpc/serving/pages.py``
``KvPageCache.admit``; a wait behind an earlier offer counts what that offer
lacks). A slot is short only while every slot is held by a sequence not yet
acknowledged: evicting an acknowledged one takes no wait. Reads 0 where no
admission waited for slots; a program whose cache keeps no state
(``kv_states_written`` did not move) gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("kv_states_written") or not c.get("kv_seqs_admitted"):
        return None
    return 100.0 * c.get("kv_admit_waits_slots", 0) / c["kv_seqs_admitted"]
