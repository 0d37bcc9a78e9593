"""Whether what the timed path produced is correct, judged against the
configuration's plain reference (`bench/references/<reference>.py`).

Compared, each against its limit in the configuration file:

  mask_mismatch   rows of the fused forward's pools whose validity differs
                  from the reference's (every forward call of the window)
  forward_gap     widest relative EDP gap of the forward on rows both call
                  valid
  dtype_mismatch  forward calls computed in another precision than the
                  configuration states
  invalid_best    best designs of finished searches that break a hardware
                  or mapping constraint, or are missing or not finite
  search_gap      widest relative gap of a finished search's per-layer EDP
                  or model EDP (their sum) from the reference's
  gp_spec_mismatch  stacked GP fits and scorings (`gp._fit_stack`,
                  `gp._score_stack`) whose float operands or results are
                  not in the precision the configuration's `surrogate`
                  states, and scorings with another kernel than it states
  gp_pick_gap     for every run of every stacked GP scoring in the window:
                  the reference GP (`bench/references/gp.py`) is fit on the
                  run's observations and scores the run's candidate pool;
                  the gap by which the program's pick lies below the
                  reference's best utility, as a share of the utility's
                  range over the pool.  The widest such gap.

`answer="control"` puts the references computed one precision lower in the
program's place: the cost model in bfloat16 for the stated float32 forward,
the GP in float32 for the stated float64 surrogate.  That is the control
the limits must reject.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

HW_ATTRS = ("pe_mesh_x", "pe_mesh_y", "lb_input", "lb_weight", "lb_output",
            "gb_entries", "gb_instances", "gb_mesh_x", "gb_mesh_y",
            "gb_block", "gb_cluster", "df_fw", "df_fh")
CONTROL_DTYPE = ml_dtypes.bfloat16
GP_CONTROL_DTYPE = np.float32


@dataclasses.dataclass
class ForwardCall:
    """One call of the fused forward in the window, as the program made it:
    its inputs and the device arrays it returned."""
    search: int
    hws: list
    pools: list
    layers: list
    out: dict
    stacked: bool


@dataclasses.dataclass
class ScoreCall:
    """One stacked GP scoring in the window, as the program made it: the
    runs' padded observations (mask 1 on real rows), their candidate pools,
    the incumbents, and the program's pick per run (device arrays)."""
    search: int
    kind: str
    X: object
    y: object
    mask: object
    feats: object
    best: object
    idx: object


class Checker:
    def __init__(self, config: dict, reference, surrogate_reference=None):
        self.ref, self.gp_ref = reference, surrogate_reference
        self.surrogate = config.get("surrogate")
        sw = config["codesign"]["sw"]
        self.acquisition = (sw["acquisition"], float(sw["lam"]))
        self.limits = config["limits"]
        self.dtype = config["forward_dtype"]
        self.budget = config["accelerator"]
        self.layers = {ly["name"]: ly for ly in config["layers"]}
        e = self.budget["energy_pj"]
        self.constants = {"dram_bandwidth": self.budget["dram_bandwidth"],
                          "e_mac": e["mac"], "e_lb": e["lb"],
                          "e_noc": e["noc"], "e_gb": e["gb"],
                          "e_dram": e["dram"]}

    def _hw(self, hw) -> dict:
        return {**{a: getattr(hw, a) for a in HW_ATTRS}, **self.constants}

    def _rows(self, hws, layer_names, factors, order_gb, order_dram, counts,
              dtype):
        """Reference over runs of rows: run k has counts[k] rows."""
        hw = {k: np.repeat([self._hw(h)[k] for h in hws], counts)
              for k in self.ref.HW_FIELDS}
        layer = {k: np.repeat([self.layers[n][k] for n in layer_names],
                              counts)
                 for k in self.ref.DIMS + ("stride",)}
        return self.ref.evaluate_rows(factors, order_gb, order_dram, hw,
                                      layer, dtype=dtype)

    def forward(self, call: ForwardCall, answer: str = "program") -> dict:
        counts = [len(p) for p in call.pools]
        cat = [np.concatenate([getattr(p, a) for p in call.pools])
               for a in ("factors", "order_gb", "order_dram")]
        names = [ly.name for ly in call.layers]
        ref = self._rows(call.hws, names, *cat, counts, np.float64)
        if answer == "program":
            valid, edp = (np.asarray(call.out[k]) for k in ("valid", "edp"))
            if call.stacked:
                valid = np.concatenate([valid[k, :n]
                                        for k, n in enumerate(counts)])
                edp = np.concatenate([edp[k, :n]
                                      for k, n in enumerate(counts)])
            dtype_ok = str(call.out["edp"].dtype) == self.dtype
        else:
            low = self._rows(call.hws, names, *cat, counts, CONTROL_DTYPE)
            valid, edp, dtype_ok = low["valid"], low["edp"], True
        edp = np.asarray(edp, np.float64)
        both = valid & ref["valid"]
        gap = np.abs(edp[both] - ref["edp"][both]) / ref["edp"][both]
        return {"mask_mismatch": int(np.sum(valid != ref["valid"])),
                "forward_gap": float(gap.max()) if gap.size else 0.0,
                "dtype_mismatch": int(not dtype_ok),
                "rows": int(sum(counts))}

    def search(self, result, answer: str = "program") -> dict:
        names = [n for n in self.layers
                 if n in (result.best_mappings or {})]
        if result.best_hw is None or not names:
            return {"invalid_best": len(self.layers) + 1, "search_gap": 0.0}
        hwd = self._hw(result.best_hw)
        invalid = int(not self.ref.hardware_is_valid(hwd, self.budget))
        invalid += len(self.layers) - len(names)
        maps = [result.best_mappings[n] for n in names]
        dim_index = {d: i for i, d in enumerate(self.ref.DIMS)}
        factors = np.array([m.factors for m in maps], np.int64)
        og, od = (np.array([[dim_index[d] for d in getattr(m, a)]
                            for m in maps], np.int64)
                  for a in ("order_gb", "order_dram"))
        counts = [1] * len(names)
        ref = self._rows([result.best_hw] * len(names), names, factors, og,
                         od, counts, np.float64)
        invalid += int(np.sum(~ref["valid"]))
        if answer == "program":
            layer_edp = np.array([result.layer_edps[n] for n in names],
                                 np.float64)
            model_edp = float(result.best_model_edp)
        else:
            low = self._rows([result.best_hw] * len(names), names, factors,
                             og, od, counts, CONTROL_DTYPE)["edp"]
            layer_edp = low
            model_edp = float(np.sum(low.astype(CONTROL_DTYPE)))
        finite = np.isfinite(layer_edp) & np.isfinite(ref["edp"])
        invalid += int(np.sum(~finite)) + int(not np.isfinite(model_edp))
        gaps = list(np.abs(layer_edp[finite] - ref["edp"][finite])
                    / ref["edp"][finite])
        if np.isfinite(model_edp) and finite.all():
            total = float(np.sum(ref["edp"]))
            gaps.append(abs(model_edp - total) / total)
        return {"invalid_best": invalid,
                "search_gap": float(max(gaps)) if gaps else 0.0}

    def gp(self, call: ScoreCall, answer: str = "program") -> dict:
        """`gp_pick_gap` over the runs of one stacked scoring."""
        X, y, mask, feats, best = (np.asarray(a, np.float64) for a in
                                   (call.X, call.y, call.mask, call.feats,
                                    call.best))
        idx = np.asarray(call.idx)
        acq, lam = self.acquisition
        widest = 0.0
        for k in range(len(X)):
            real = mask[k] > 0.5
            args = (X[k][real], y[k][real], feats[k], best[k, 0],
                    self.surrogate, acq, lam)
            util = self.gp_ref.utilities(*args)
            if answer == "program":
                pick = int(idx[k])
            else:
                low = self.gp_ref.utilities(*args, dtype=GP_CONTROL_DTYPE)
                pick = int(np.nanargmax(low)) if np.isfinite(low).any() else 0
            span = util.max() - util.min()
            if not (np.isfinite(util).all() and 0 <= pick < len(util)):
                gap = 1.0  # no answer to compare counts as the widest gap
            else:
                gap = (util.max() - util[pick]) / span if span > 0 else 0.0
            widest = max(widest, float(gap))
        return {"gp_pick_gap": widest}

    def judge(self, recorded, results, answer: str = "program") -> dict:
        """Readings over the window (`recorded`: the calls a `run.Recorder`
        kept), the limits, and `failed` (searches at fault).  Counts add up
        over the window; gaps take the widest."""
        per_search = {i: {} for i in range(len(results))}
        rows = picks = 0
        for call in recorded.calls:
            r = self.forward(call, answer)
            rows += r.pop("rows")
            _merge(per_search.setdefault(call.search, {}), r)
        stated = self.surrogate["dtype"]
        for search, dtypes in recorded.gp_dtypes:
            bad = int(answer == "program" and dtypes != {stated})
            _merge(per_search.setdefault(search, {}),
                   {"gp_spec_mismatch": bad})
        for call in recorded.gp_calls:
            picks += len(np.asarray(call.idx))
            into = per_search.setdefault(call.search, {})
            if call.kind != self.surrogate["kernel"]:
                _merge(into, {"gp_spec_mismatch": 1})
                continue
            _merge(into, self.gp(call, answer))
        for i, res in enumerate(results):
            _merge(per_search[i], self.search(res, answer))
        total = {k: 0 for k in self.limits}
        failed = 0
        for readings in per_search.values():
            _merge(total, readings)
            failed += any(v > self.limits[k] for k, v in readings.items())
        checks = {k: {"value": total[k], "limit": self.limits[k]}
                  for k in self.limits}
        correct = (bool(results) and rows > 0 and picks > 0
                   and all(c["value"] <= c["limit"] for c in checks.values()))
        return {"correct": correct, "failed": failed, "rows": rows,
                "picks": picks, "checks": checks}


def _merge(into: dict, readings: dict) -> None:
    for k, v in readings.items():
        if isinstance(v, int):
            into[k] = into.get(k, 0) + v
        else:
            into[k] = max(into.get(k, 0.0), v)
