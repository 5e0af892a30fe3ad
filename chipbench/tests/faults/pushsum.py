"""The faults planted in the timed path of an edge-partitioned push-sum
cell (``chipbench/engines/pushsum.py``); each must come out not correct.

Each fault is a function ``(monkeypatch, workload) -> hook | None``: it
breaks the program through ``monkeypatch``, or returns a ``hook(cell)``
that wraps the built cell before its warm call (``run.run_cell``'s
``cell_hook``).
"""


def _halo_left_out(monkeypatch, workload):
    """The halo left out: each chip keeps its own receiver partials."""
    from repro.core import pushsum

    monkeypatch.setattr(pushsum, "_halo", lambda recv, recv_m, *a: (
        recv, recv_m))
    return None


def _mask_window_zero(monkeypatch, workload):
    """Every chip takes window 0 of the round's mask, not its own."""
    import jax
    from repro.core import pushsum, sweeps

    def window_zero(key, t, e_shard, drop_prob, B, *, graph_axis,
                    n_shards, fold_t=None):
        full = pushsum.step_edge_mask(key, t, n_shards * e_shard, drop_prob,
                                      B, fold_t=fold_t)
        return jax.lax.dynamic_slice(full, (0,), (e_shard,))
    monkeypatch.setattr(sweeps, "shard_edge_mask", window_zero)
    return None


def _exchange_returns_state(monkeypatch, workload):
    """The exchange step returns its state unchanged: push-sum delivers
    nothing."""
    from repro.core import sweeps

    monkeypatch.setattr(sweeps, "sparse_pushsum_step",
                        lambda state, *a, **k: state)
    return None


def _one_shard_dropped(monkeypatch, workload):
    """One shard's edges dropped: the second shard's edges carry
    nothing."""
    import dataclasses

    def hook(cell):
        valid = cell._shards.valid.copy()
        valid[1] = False
        cell._shards = dataclasses.replace(cell._shards, valid=valid)
        return cell
    return hook


def _answer_altered(monkeypatch, workload):
    """One answer altered where it is produced."""
    def hook(cell):
        keep = cell.keep

        def altered(out):
            ratio, err = keep(out)
            return ratio.at[0, 0].set(ratio[0, 0][::-1]), err
        cell.keep = altered
        return cell
    return hook


FAULTS = [_halo_left_out, _mask_window_zero, _exchange_returns_state,
          _one_shard_dropped, _answer_altered]
