"""Host tails of the kernel launches: launch outputs -> resolved tickets.

Given a flush's launch outputs as numpy arrays, each resolver
de-randomizes and verifies on the controller side, bumps the owning
chips' functional counters and resolves the tickets.  Each returns the
exact device->host result payload in bytes (the
``BackendStats.result_bytes`` contract); with lazy tickets they run at the
first ``result()`` call of a burst, not at flush.
"""
from __future__ import annotations

import numpy as np

from repro.core import ecc
from repro.core.bits import CHUNK_BYTES, CHUNKS_PER_PAGE, SLOTS_PER_CHUNK, \
    popcount_words, slot_words_to_bytes, unpack_bitmap
from repro.core.commands import GatherResponse, LookupResponse, SearchResponse
from repro.core.ecc import OpenVerdict
from repro.core.randomize import chunk_stream_words_batch
from repro.kernels.sim_fused.sim_fused import NO_SLOT


def _resolve_bitmap_responses(chips, cmds, placements, out, matches_of,
                              reliability=None, opens=None,
                              is_plan=False) -> int:
    """Resolve bitmap-shaped (search / plan) tickets from launch output.

    ``placements[i]`` is the index tuple of command i's bitmap in ``out``
    (``(ci, qi, pi)`` for a chip-stacked launch).  Commands that dedup'd
    into the same launch cell share ONE host copy of the bitmap (and its
    popcount) — one copy per unique placement, detached from ``out`` so
    later mutation of the launch buffer can never alias into a response.
    ``matches_of(cmd)``
    is the on-chip match-op count the command's chip executed (1 for a
    search, ``n_passes`` for a plan).  Returns result bytes: 64 B per
    unique placement (shared cells cross the link once).

    With a reliability tier attached, each unique cell's raw bitmap runs
    the vote/verify/fallback finalize against the flush's captured page
    opens; uncorrectable pages fail every ticket of the cell with the
    typed error instead of resolving.
    """
    from repro.reliability import UncorrectableReadError
    cache: dict[tuple, tuple] = {}
    n_ok = 0
    for (cmd, ticket), idx in zip(cmds, placements):
        entry = cache.get(idx)
        if entry is None:
            raw = np.array(out[idx], copy=True)
            if reliability is None:
                entry = ("ok", SearchResponse(
                    bitmap_words=raw,
                    match_count=int(popcount_words(raw).sum()),
                    open_verdict=OpenVerdict.CLEAN.value))
            else:
                try:
                    fin = (reliability.finalize_plan if is_plan
                           else reliability.finalize_search)
                    entry = ("ok", fin(chips, cmd, raw, opens))
                except UncorrectableReadError as e:
                    entry = ("err", e)
            cache[idx] = entry
            if entry[0] == "ok":
                n_ok += 1
        chip, _ = chips.route(cmd.page_addr)
        chip.counters.searches += matches_of(cmd)
        if entry[0] == "ok":
            ticket._resolve(entry[1])
        else:
            ticket._fail(entry[1])
    return 64 * n_ok


def resolve_search_responses(chips, searches, placements, out,
                             reliability=None, opens=None) -> int:
    return _resolve_bitmap_responses(chips, searches, placements, out,
                                     lambda cmd: 1, reliability, opens)


def resolve_plan_responses(chips, plans, placements, out,
                           reliability=None, opens=None) -> int:
    """A PLAN's chip executed ``n_passes`` match ops, but only the one
    combined 64 B bitmap per unique cell crossed — the Fig 10 win."""
    return _resolve_bitmap_responses(chips, plans, placements, out,
                                     lambda cmd: cmd.n_passes, reliability,
                                     opens, is_plan=True)


def snapshot_parities(chips, addrs) -> dict:
    """Flush-time copy of each page's inner-code parities.

    Lazy host tails verify CRCs at drain time, which may be AFTER a
    reprogram of one of the burst's pages; the launch itself captured the
    pre-write plane snapshot, so the verification must compare against
    the parities as of flush, not whatever the chip holds at drain.
    """
    snap = {}
    for a in set(addrs):
        chip, local = chips.route(a)
        snap[int(a)] = chip.pages[local].chunk_parities.copy()
    return snap


def resolve_lookup_responses(chips, lookups, bm, val, slots,
                             parity_snap, reliability=None,
                             opens=None) -> int:
    """Fused-lookup host tail: batched de-randomize + inner-code verify of
    every hit's value chunk, then ticket resolution.

    ``bm`` (n, 16), ``val`` (n, 16), ``slots`` (n,) are the launch outputs
    trimmed to the burst length; ``parity_snap`` maps each value page to
    its flush-time ``snapshot_parities`` row.

    With a reliability tier attached the on-device slot select and value
    gather are advisory only: the finalize path re-derives the slot from
    the voted/verified key bitmap and host-reads the value chunk from the
    current image, so every backend serves byte-identical values under a
    fault seed.
    """
    if reliability is not None:
        return _resolve_lookups_reliable(chips, lookups, bm, reliability,
                                         opens)
    n = len(lookups)
    key_addrs = [cmd.page_addr for cmd, _ in lookups]
    val_addrs = [cmd.value_page for cmd, _ in lookups]
    counts = popcount_words(bm)                # (n,) per-row match totals

    for a in set(key_addrs):
        chip, _ = chips.route(a)
        chip.counters.array_reads += 1

    hit = slots < NO_SLOT
    hit_idx = np.nonzero(hit)[0]
    values = [None] * n
    parity = np.ones(n, dtype=bool)
    if hit_idx.size:
        v_locals, v_seeds, parities = [], [], []
        chunks = slots[hit_idx] // SLOTS_PER_CHUNK
        for i, c in zip(hit_idx, chunks):
            chip, local = chips.route(val_addrs[int(i)])
            v_locals.append(local)
            v_seeds.append(chip.device_seed & 0xFFFFFFFF)
            parities.append(parity_snap[int(val_addrs[int(i)])][int(c)])
            chip.counters.array_reads += 1
            chip.counters.gathers += 1
            chip.counters.chunks_gathered += 1
        streams = chunk_stream_words_batch(v_locals, chunks, v_seeds)
        words = val[hit_idx].reshape(-1, SLOTS_PER_CHUNK, 2)
        plain = slot_words_to_bytes(words ^ streams)       # (K, 64) bytes
        parity[hit_idx] = (ecc.crc32_rows(plain)
                           == np.asarray(parities, np.uint32))
        offs = (slots[hit_idx] % SLOTS_PER_CHUNK) * 8
        for j, i in enumerate(hit_idx):
            values[int(i)] = bytes(plain[j, offs[j]:offs[j] + 8])

    for i, (cmd, ticket) in enumerate(lookups):
        chip, _ = chips.route(cmd.page_addr)
        chip.counters.searches += 1
        resp = SearchResponse(bitmap_words=bm[i].copy(),
                              match_count=int(counts[i]),
                              open_verdict=OpenVerdict.CLEAN.value)
        ticket._resolve(LookupResponse(
            search=resp,
            value_slot=int(slots[i]) if hit[i] else None,
            value=values[i], parity_ok=bool(parity[i])))
    return 64 * n + 64 * int(hit_idx.size)


def _resolve_lookups_reliable(chips, lookups, bm, reliability, opens) -> int:
    """Reliability tail for a lookup burst: finalize each key bitmap
    (vote + selective verification + miss fallback) and serve the value
    through the inner-code-checked host read."""
    from repro.reliability import UncorrectableReadError
    nbytes = 0
    for a in {cmd.page_addr for cmd, _ in lookups}:
        chip, _ = chips.route(a)
        chip.counters.array_reads += 1
    for i, (cmd, ticket) in enumerate(lookups):
        chip, _ = chips.route(cmd.page_addr)
        chip.counters.searches += 1
        try:
            resp = reliability.finalize_lookup(
                chips, cmd, np.array(bm[i], copy=True), opens)
        except UncorrectableReadError as e:
            ticket._fail(e)
            continue
        ticket._resolve(resp)
        nbytes += 64 + (64 if resp.value_slot is not None else 0)
    return nbytes


def resolve_gather_responses(chips, gathers, out, parity_snap,
                             reliability=None, opens=None) -> int:
    """Gather host tail: one stream regeneration + one CRC pass for every
    selected chunk of the whole burst.  ``parity_snap`` holds each page's
    flush-time ``snapshot_parities`` row.  Returns result bytes (64 B per
    gathered chunk)."""
    owners, all_locals, all_chunks, all_seeds, all_parities = \
        [], [], [], [], []
    chunk_ids_per = []
    for cmd, _ in gathers:
        chip, local = chips.route(cmd.page_addr)
        owners.append((chip, local))
        bits = unpack_bitmap(np.asarray(cmd.chunk_bitmap, np.uint32),
                             n_bits=CHUNKS_PER_PAGE)
        chunk_ids = np.nonzero(bits)[0]
        chunk_ids_per.append(chunk_ids)
        all_locals.extend([local] * chunk_ids.size)
        all_chunks.extend(chunk_ids.tolist())
        all_seeds.extend([chip.device_seed & 0xFFFFFFFF]
                         * chunk_ids.size)
        all_parities.append(parity_snap[int(cmd.page_addr)][chunk_ids])

    k_total = len(all_chunks)
    if k_total:
        words = np.concatenate([
            out[r, :ids.size] for r, ids in enumerate(chunk_ids_per)
            if ids.size]).reshape(k_total, SLOTS_PER_CHUNK, 2)
        streams = chunk_stream_words_batch(all_locals, all_chunks,
                                           all_seeds)
        plain_all = slot_words_to_bytes(words ^ streams)
        parity_all = (ecc.crc32_rows(plain_all)
                      == np.concatenate(all_parities))
    else:
        plain_all = np.zeros((0, CHUNK_BYTES), dtype=np.uint8)
        parity_all = np.zeros(0, dtype=bool)

    from repro.reliability import UncorrectableReadError
    pos = 0
    for r, (cmd, ticket) in enumerate(gathers):
        chip, local = owners[r]
        chunk_ids = chunk_ids_per[r]
        k = int(chunk_ids.size)
        plain = plain_all[pos:pos + k]
        parity_ok = parity_all[pos:pos + k]
        pos += k
        chip.counters.array_reads += 1
        chip.counters.gathers += 1
        chip.counters.chunks_gathered += k
        resp = GatherResponse(chunks=plain, chunk_ids=chunk_ids,
                              parity_ok=parity_ok)
        if reliability is not None:
            try:
                resp = reliability.finalize_gather(chips, cmd, resp, opens)
            except UncorrectableReadError as e:
                ticket._fail(e)
                continue
        ticket._resolve(resp)
    return 64 * k_total
