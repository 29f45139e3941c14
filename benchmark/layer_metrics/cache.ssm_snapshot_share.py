"""Share of the stride ends the window's rows crossed for which the
dispatch was given an entry of the snapshot pool:
`batch_ssm_snapshots_total` over `batch_ssm_stride_ends_total`, both counted
where a dispatch is issued (`runtime/batch_engine.py _state_word`; a stride
end is a position p with (p + 1) % 256 == 0). 100 where every stride end a
request crossed can seed another request, a rewind or a resume; less where
every entry of the pool was being written by a dispatch in flight and the
row's state went to the scratch entry. Entries given up for newer ones
(`paged_kv_ssm_snapshot_evictions_total`, printed) do not lower it: they cost
an older landing, not this one. A program without the counters, or a model
without state-space layers, reads nothing."""
UNIT = "%"
LAYER = "cache"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    snaps = ctx.counter_delta("batch_ssm_snapshots_total")
    ends = ctx.counter_delta("batch_ssm_stride_ends_total")
    if snaps is None or not ends:
        print("cache.ssm_snapshot_share: the program counts no stride ends "
              "(no state-space layers), or none was crossed in the window",
              flush=True)
        return None
    gone = ctx.counter_delta("paged_kv_ssm_snapshot_evictions_total") or 0.0
    print(f"cache.ssm_snapshot_share: {snaps:.0f} snapshots of {ends:.0f} "
          f"stride ends; {gone:.0f} older entries given up for them",
          flush=True)
    return 100.0 * snaps / ends
