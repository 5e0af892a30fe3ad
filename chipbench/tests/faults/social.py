"""The faults planted in the timed path of a social cell
(``chipbench/engines/social.py``); each must come out not correct.

Each fault is a function ``(monkeypatch, workload) -> hook | None``: it
breaks the program through ``monkeypatch``, or returns a ``hook(cell)``
that wraps the built cell before its warm call (``run.run_cell``'s
``cell_hook``).
"""


def _exchange_returns_state(monkeypatch, workload):
    """The exchange step returns its state unchanged: push-sum delivers
    nothing (social), the trimmed gossip hears no neighbour (Byzantine)."""
    from repro.core import social

    monkeypatch.setattr(social, "sparse_pushsum_step",
                        lambda state, *a, **k: state)
    return None


def _half_the_batch(monkeypatch, workload):
    """Half of the batch left out: the second half of the edges carries
    nothing (social), the second half of the grid's seeds repeats the
    first half (Byzantine)."""
    def hook(cell):
        valid = cell._rt.valid
        cell._rt = cell._rt._replace(
            valid=valid.at[valid.shape[0] // 2:].set(False))
        return cell
    return hook


def _ps_fusion_broken(monkeypatch, workload):
    """The parameter server's fusion broken: left out (social: the
    representatives keep their own values), or its trim dropped
    (Byzantine: the pool's F largest and F smallest answers, a Byzantine
    representative's among them, are averaged in)."""
    from repro.core import social

    monkeypatch.setattr(social, "hps_fusion", lambda z, m, *a, **k: (z, m))
    return None


def _answer_altered(monkeypatch, workload):
    """One answer altered where it is produced."""
    def hook(cell):
        keep = cell.keep

        def altered(out):
            kept = list(keep(out))
            kept[0] = kept[0].at[0].set(kept[0][0][::-1])
            return tuple(kept)
        cell.keep = altered
        return cell
    return hook


FAULTS = [_exchange_returns_state, _half_the_batch, _ps_fusion_broken,
          _answer_altered]
