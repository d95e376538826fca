"""What decides ``correct``: the comparison of what the window's last
finished build wrote with the plain reference under
``benchmarks/reference/``.

Two steps. :func:`read_build` reads back what the build left in the
store and under the models directory, and counts the exact guarantees
it broke (``violations``, limit 0) - among them that every checkpoint
was written since that build was posted. :class:`Comparison` then holds
those outputs against answers the reference makes from the seed's rows
alone. Which
numbers are compared follows from the configuration's ``classifiers``
(``NUMBERS``); each has its limit in the configuration's ``limits``,
set from readings that PERF.md gives.
"""

from __future__ import annotations

import json
import os
import zipfile

import numpy as np

# what each classifier of a configuration adds to the comparison
NUMBERS = {
    "lr": ["lr_prob_gap"],
    "nb": ["nb_pred_gap", "nb_theta_gap", "nb_prior_gap"],
    "dt": ["tree_pred_gap", "dt_leaf_gap", "dt_split_gap", "dt_loss_gap"],
    "rf": ["tree_pred_gap", "rf_leaf_z", "rf_split_gap", "ensemble_loss_gap"],
    "gb": ["tree_pred_gap", "gb_leaf_gap", "gb_split_gap", "ensemble_loss_gap"],
}
TREES = ("dt", "rf", "gb")
# a file's time comes from the kernel's coarse clock, which may lag the
# instant the build was posted at by a tick
MTIME_SLACK_S = 0.05


def expected_numbers(classifiers: list[str]) -> list[str]:
    out: list[str] = []
    for clf in classifiers:
        out += [name for name in NUMBERS[clf] if name not in out]
    return out


def read_checkpoint(path: str) -> dict:
    """A published model artifact: the arrays and the header's scalars."""
    with zipfile.ZipFile(path) as archive:
        header = json.loads(archive.read("__model__.json"))
    data = np.load(path)
    return {"kind": header["kind"], **header["scalars"], **{k: data[k] for k in data.files}}


def sample_rows(seed: int, n_test: int, size: int) -> np.ndarray:
    """The test rows whose stored probabilities are compared: drawn from
    the seed, sorted."""
    rng = np.random.default_rng([int(seed), 15485863])
    return np.sort(rng.choice(n_test, size=min(n_test, size), replace=False))


def read_build(system, ref, config: dict, names: dict, models_dir: str,
               since: float, y_test: np.ndarray, sample: np.ndarray):
    """What the build posted at ``since`` left behind, per classifier:
    the metadata row, the published model, the stored probabilities on
    the sampled rows and the stored answers' log-loss. Returns
    ``(outputs, violations)``."""
    outputs, violations = {}, []
    for clf in config["classifiers"]:
        name = f"{names['test']}_prediction_{clf}"
        meta = system.store.find_one(name, {"_id": 0})
        if not meta or meta.get("classificator") != clf:
            violations.append(f"{clf}: no metadata row")
            continue
        path = meta.get("model_checkpoint") or ""
        if not os.path.isfile(path) or os.path.dirname(path) != models_dir:
            violations.append(f"{clf}: no published checkpoint")
            continue
        if os.path.getmtime(path) < since - MTIME_SLACK_S:
            violations.append(f"{clf}: the checkpoint was not written by the window's last build")
        stored = system.stored(name, ["label", "prediction", "probability"])
        if len(stored["prediction"]) != len(y_test):
            violations.append(
                f"{clf}: {len(stored['prediction'])} rows stored, "
                f"{len(y_test)} in the test split"
            )
            continue
        labels = stored["prediction"].astype(np.int64)
        proba = stored["probability"]
        if not np.array_equal(stored["label"].astype(np.int64), y_test):
            violations.append(f"{clf}: stored rows are not the test rows in order")
        decided = np.abs(proba[:, 1] - 0.5) > 1e-6
        if np.any((labels != (proba[:, 1] > 0.5))[decided]):
            violations.append(f"{clf}: a stored label is not its probability's")
        accuracy, f1 = ref.accuracy_f1(labels, y_test)
        if (
            abs(accuracy - float(meta["accuracy"])) > 1e-5
            or abs(f1 - float(meta["F1"])) > 1e-5
        ):
            violations.append(
                f"{clf}: stored accuracy/F1 {meta['accuracy']}/{meta['F1']} "
                f"but the stored labels give {accuracy:.7f}/{f1:.7f}"
            )
        outputs[clf] = {
            "model": read_checkpoint(path),
            "proba": np.asarray(proba[sample, 1], dtype=np.float64),
            "logloss": ref.logloss(proba[:, 1], y_test),
            "accuracy": accuracy,
        }
    return outputs, violations


def drop_build(system, config: dict, names: dict, models_dir: str) -> None:
    """Takes away what a build wrote, so that whatever is found after
    the window was written in it."""
    for clf in config["classifiers"]:
        system.store.drop(f"{names['test']}_prediction_{clf}")
    if os.path.isdir(models_dir):
        for entry in os.listdir(models_dir):
            os.remove(os.path.join(models_dir, entry))


def model_proba(ref, model: dict, X: np.ndarray) -> np.ndarray:
    """What a model in the published layout gives for ``X`` when the
    plain reference applies it."""
    if model["kind"] == "naive_bayes":
        return ref.nb_proba((model["theta"], model["prior"]), X)
    if model["kind"] == "gbt":
        return ref.gbt_proba(
            X, model["f0"], model["step"], model["features_heap"],
            model["thresholds_heap"], model["leaf_values"], model["max_depth"],
        )
    return ref.ensemble_proba(
        X, model["features_heap"], model["thresholds_heap"],
        model["leaf_probs"], model["max_depth"],
    )


class Comparison:
    """The reference's side for one seed's rows: its own fits, its own
    quantile candidates, and the audits of models in the published
    layout (the build's, or the control's)."""

    def __init__(self, ref, config: dict, seed: int, X_train, y_train, X_test, y_test):
        self.ref = ref
        self.config = config
        self.hyper = config["hyper"]
        self.seed = int(seed)
        self.X_train, self.y_train = X_train, y_train
        self.X_test, self.y_test = X_test, y_test
        self.sample = sample_rows(seed, len(X_test), int(config["correct"]["sample_rows"]))
        self.X_sample = X_test[self.sample]
        self.numbers: dict[str, float] = {}
        self.violations: list[str] = []
        self.fits: dict = {}
        self.details: dict = {}
        classifiers = config["classifiers"]
        if "lr" in classifiers:
            self.fits["lr"] = ref.lr_fit(X_train, y_train)
        if "nb" in classifiers:
            self.fits["nb"] = ref.nb_fit(X_train, y_train, self.hyper["nb_smoothing"])
        if any(clf in classifiers for clf in TREES):
            bins = int(self.hyper["max_bins"])
            self.thresholds = ref.quantile_thresholds(X_train, bins)
            self.bins = ref.bin_matrix(X_train, self.thresholds)
            tree = ref.grow_model(
                self.bins, self.thresholds, y_train, bins, int(self.hyper["max_depth"])
            )
            self.own_tree_loss = ref.logloss(model_proba(ref, tree, X_test)[:, 1], y_test)

    def widest(self, name: str, value: float) -> None:
        value = float(value)
        if not np.isfinite(value):
            value = float("inf")
        self.numbers[name] = max(self.numbers.get(name, 0.0), value)

    def big(self, weight: np.ndarray) -> np.ndarray:
        """Nodes and leaves that hold a thousandth of the weight or more."""
        return weight >= 1e-3 * weight.max()

    def drawn(self, count: int, total: int, salt: int, first: int = 0) -> list[int]:
        """``first`` leading indexes and ``count`` more drawn from the
        seed, of ``total`` trees or rounds."""
        rng = np.random.default_rng([self.seed, salt])
        rest = np.arange(min(first, total), total)
        more = rng.choice(rest, size=min(count, len(rest)), replace=False)
        return sorted(set(range(min(first, total))) | set(int(i) for i in more))

    # --- audits of a model in the published layout ----------------------
    def audit_lr(self, proba_sample: np.ndarray) -> dict:
        want = self.ref.lr_proba(self.fits["lr"], self.X_sample)[:, 1]
        return {"lr_prob_gap": np.abs(proba_sample - want).max()}

    def audit_nb(self, model: dict) -> dict:
        theta, prior = self.fits["nb"]
        return {
            "nb_theta_gap": np.abs(theta - model["theta"]).max(),
            "nb_prior_gap": np.abs(prior - model["prior"]).max(),
        }

    def split_gap(self, best, target, chosen) -> float:
        """The share of one tree's best gains, summed over its nodes,
        by which its published splits fall short of ``target``. A sum
        and not the worst node: a deep node's gain is a small difference
        of large float32 sums, and its own share swings with them."""
        most = np.maximum(best, 0.0).sum()
        if not most > 0:
            return float("inf")
        return float(np.maximum(target - chosen, 0.0).sum() / most)

    def audit_classification_trees(self, model: dict, trees: list[int]):
        """Routes all training rows down every tree; for ``trees`` also
        the gains. Yields ``(t, leaf_sums, gains)``, ``gains`` being
        ``(best, own, chosen, weight)`` per node or None."""
        depth, bins = int(model["max_depth"]), int(self.hyper["max_bins"])
        channels = self.ref.class_channels(self.y_train)
        for t in range(len(model["features_heap"])):
            leaf = self.ref.route_blocks(
                self.X_train, model["features_heap"][t], model["thresholds_heap"][t], depth
            )
            if t in trees:
                *gains, sums = self.ref.audit_tree(
                    self.bins, leaf, model["features_heap"][t], channels, "gini", depth, bins
                )
                yield t, sums, gains
            else:
                yield t, self.ref.leaf_sums(leaf, channels, depth), None

    def audit_dt(self, model: dict) -> dict:
        (_, sums, (best, _, chosen, _)), = self.audit_classification_trees(model, [0])
        shares, count = self.ref.leaf_shares(sums)
        gap = np.abs(shares - model["leaf_probs"][0].astype(np.float64))
        return {
            "dt_leaf_gap": gap[self.big(count)].max(),
            "dt_split_gap": self.split_gap(best, best, chosen),
        }

    def audit_rf(self, model: dict) -> dict:
        """A forest's leaves carry its own bootstrap, so a leaf's
        published share is held against the share of all rows in units
        of the bootstrap's standard error, ``sqrt(p (1 - p) / n)``: the
        root mean square over all well-filled leaves is 1 for a Poisson
        bootstrap of all the rows. Gains on trees drawn from the seed:
        a node's feature is the best of the fitter's random subset, so
        the published threshold is held against the best *of its
        feature*."""
        total = len(model["features_heap"])
        checked = self.drawn(int(self.config["correct"]["rf_trees_checked"]), total, 49979687)
        z_squares, split = [], 0.0
        for t, sums, gains in self.audit_classification_trees(model, checked):
            shares, count = self.ref.leaf_shares(sums)
            keep = self.big(count) & (shares[:, 1] > 0) & (shares[:, 1] < 1)
            error = np.sqrt(shares[keep, 1] * shares[keep, 0] / count[keep])
            z = (model["leaf_probs"][t][keep, 1].astype(np.float64) - shares[keep, 1]) / error
            z_squares.append(z**2)
            if gains is not None:
                best, own, chosen, _ = gains
                split = max(split, self.split_gap(best, own, chosen))
        z_squares = np.concatenate(z_squares) if z_squares else np.array([np.inf])
        return {
            "rf_leaf_z": abs(float(np.sqrt(z_squares.mean())) - 1.0),
            "rf_split_gap": split,
        }

    def audit_gb(self, model: dict) -> dict:
        """Every round's leaf values against the Newton step that the
        rows in the leaf ask for, gradients from the published earlier
        rounds; gains on the first rounds and on some drawn from the seed."""
        depth, bins = int(model["max_depth"]), int(self.hyper["max_bins"])
        lam, floor = self.hyper["gbt_lambda"], self.hyper["gbt_hessian_floor"]
        total = len(model["features_heap"])
        checked = self.drawn(
            int(self.config["correct"]["gbt_rounds_drawn"]), total, 67867967,
            first=int(self.config["correct"]["gbt_rounds_first"]),
        )
        y = self.y_train.astype(np.float64)
        margins = np.full(len(y), float(model["f0"]))
        leaf_gap, split = 0.0, 0.0
        for t in range(total):
            p = 1.0 / (1.0 + np.exp(-margins))
            channels = [p - y, np.maximum(p * (1 - p), floor)]
            leaf = self.ref.route_blocks(
                self.X_train, model["features_heap"][t], model["thresholds_heap"][t], depth
            )
            if t in checked:
                best, _, chosen, _, sums = self.ref.audit_tree(
                    self.bins, leaf, model["features_heap"][t], channels, "newton",
                    depth, bins, lam,
                )
                split = max(split, self.split_gap(best, best, chosen))
            else:
                sums = self.ref.leaf_sums(leaf, channels, depth)
            values = -sums[0] / (sums[1] + lam)
            published = model["leaf_values"][t].astype(np.float64)
            leaf_gap = max(leaf_gap, np.abs(values - published)[self.big(sums[1])].max())
            margins += float(model["step"]) * published[leaf]
        return {"gb_leaf_gap": leaf_gap, "gb_split_gap": split}

    def loss_gap(self, logloss: float) -> float:
        """How far a tree model's log-loss on the test split lies above
        that of the reference's own decision tree, as a share of it."""
        return (logloss - self.own_tree_loss) / self.own_tree_loss

    # --- the build ------------------------------------------------------
    def compare(self, outputs: dict) -> None:
        for clf, out in outputs.items():
            model = out["model"]
            self.details[clf] = {"logloss": out["logloss"], "accuracy": out["accuracy"]}
            if clf == "lr":
                found = self.audit_lr(out["proba"])
            else:
                followed = model_proba(self.ref, model, self.X_sample)[:, 1]
                pred = "nb_pred_gap" if clf == "nb" else "tree_pred_gap"
                found = {pred: np.abs(out["proba"] - followed).max()}
                found.update(getattr(self, f"audit_{clf}")(model))
                if clf == "dt":
                    found["dt_loss_gap"] = abs(self.loss_gap(out["logloss"]))
                elif clf in TREES:
                    found["ensemble_loss_gap"] = self.loss_gap(out["logloss"])
            for name, value in found.items():
                self.widest(name, value)

    def verdict(self) -> tuple[bool, dict]:
        limits = self.config["limits"]
        compared = {}
        ok = True
        for name in expected_numbers(self.config["classifiers"]):
            value = self.numbers.get(name, float("inf"))
            limit = float(limits[name])
            compared[name] = {"value": value, "limit": limit}
            ok = ok and value <= limit
        compared["violations"] = {"value": len(self.violations), "limit": 0}
        ok = ok and not self.violations
        return ok, compared


def control_numbers(comparison: Comparison, outputs: dict) -> dict:
    """The control: the reference put in the program's place and computed
    on bfloat16 features, the precision below the float32 that the
    configuration states. Its models, in the published layout, go through
    the same audits; each reading is what the same number shows then."""
    c, ref = comparison, comparison.ref
    hyper, classifiers = c.hyper, c.config["classifiers"]
    low = ref.to_bfloat16
    X_low, sample_low, test_low = low(c.X_train), low(c.X_sample), low(c.X_test)
    out = {}
    if "lr" in classifiers:
        fit = ref.lr_fit(X_low, c.y_train)
        out.update(c.audit_lr(ref.lr_proba(fit, sample_low)[:, 1]))
    if "nb" in classifiers:
        theta, prior = ref.nb_fit(X_low, c.y_train, hyper["nb_smoothing"])
        out.update(c.audit_nb({"theta": theta, "prior": prior}))
    gaps = [
        np.abs(
            model_proba(ref, entry["model"], sample_low)[:, 1]
            - model_proba(ref, entry["model"], c.X_sample)[:, 1]
        ).max()
        for clf, entry in outputs.items() if clf != "lr"
    ]
    for clf, gap in zip([k for k in outputs if k != "lr"], gaps):
        name = "nb_pred_gap" if clf == "nb" else "tree_pred_gap"
        out[name] = max(out.get(name, 0.0), float(gap))
    if not any(clf in classifiers for clf in TREES):
        return out
    bins, depth = int(hyper["max_bins"]), int(hyper["max_depth"])
    cuts = ref.quantile_thresholds(X_low, bins)
    binned = ref.bin_matrix(X_low, cuts)
    if "dt" in classifiers:
        tree = ref.grow_model(binned, cuts, c.y_train, bins, depth)
        out.update(c.audit_dt(tree))
        loss = ref.logloss(model_proba(ref, tree, test_low)[:, 1], c.y_test)
        out["dt_loss_gap"] = abs(c.loss_gap(loss))
    if "rf" in classifiers:
        forest = ref.grow_forest(
            binned, cuts, c.y_train, bins, depth,
            trees=int(c.config["correct"]["rf_trees_checked"]), seed=c.seed,
        )
        out.update(c.audit_rf(forest))
        loss = ref.logloss(model_proba(ref, forest, test_low)[:, 1], c.y_test)
        out["ensemble_loss_gap"] = c.loss_gap(loss)
    if "gb" in classifiers:
        rounds = int(c.config["correct"]["gbt_rounds_first"]) + 1
        boosted = ref.grow_boosted(
            binned, cuts, c.y_train, bins, depth, rounds, hyper["gbt_step"],
            hyper["gbt_lambda"], hyper["gbt_hessian_floor"],
        )
        out.update(c.audit_gb(boosted))
    return {name: float(value) for name, value in out.items()}


def fault_numbers(comparison: Comparison, outputs: dict) -> dict:
    """The reference put in the program's place with a fault planted, at
    the cell's own size: ``half_batch`` (every fit sees the first half of
    the rows and takes it for all), ``first_feature`` (every node of
    every tree may split on the first column alone), ``wrong_split``
    (naive Bayes fitted to the test rows) and, for the published forest,
    ``random_threshold`` (every node keeps its feature and takes another
    of its candidates at random). ``{fault: readings}``."""
    c, ref = comparison, comparison.ref
    hyper, classifiers, options = c.hyper, c.config["classifiers"], c.config["correct"]
    bins, depth = int(hyper["max_bins"]), int(hyper["max_depth"])
    trees, rounds = int(options["rf_trees_checked"]), int(options["gbt_rounds_first"]) + 1
    boost = (hyper["gbt_step"], hyper["gbt_lambda"], hyper["gbt_hessian_floor"])
    half = len(c.X_train) // 2
    X, y = c.X_train[:half], c.y_train[:half]
    out = {"half_batch": {}, "first_feature": {}, "wrong_split": {}, "random_threshold": {}}
    if "nb" in classifiers:
        theta, prior = ref.nb_fit(X, y, hyper["nb_smoothing"])
        out["half_batch"].update(c.audit_nb({"theta": theta, "prior": prior}))
        theta, prior = ref.nb_fit(c.X_test, c.y_test, hyper["nb_smoothing"])
        out["wrong_split"].update(c.audit_nb({"theta": theta, "prior": prior}))
    if any(clf in classifiers for clf in TREES):
        cuts = ref.quantile_thresholds(X, bins)
        binned = ref.bin_matrix(X, cuts)

        def first_only(level):
            mask = np.zeros((1 << level, c.bins.shape[1]), dtype=bool)
            mask[:, 0] = True
            return mask

        def loss(model):
            return ref.logloss(model_proba(ref, model, c.X_test)[:, 1], c.y_test)

    if "dt" in classifiers:
        out["half_batch"].update(c.audit_dt(ref.grow_model(binned, cuts, y, bins, depth)))
        poor = ref.grow_model(c.bins, c.thresholds, c.y_train, bins, depth, allowed=first_only)
        out["first_feature"].update(c.audit_dt(poor), dt_loss_gap=abs(c.loss_gap(loss(poor))))
    if "rf" in classifiers:
        forest = ref.grow_forest(binned, cuts, y, bins, depth, trees, c.seed)
        out["half_batch"].update(c.audit_rf(forest))
        poor = ref.grow_forest(
            c.bins, c.thresholds, c.y_train, bins, depth, trees, c.seed, allowed=first_only
        )
        out["first_feature"]["ensemble_loss_gap"] = c.loss_gap(loss(poor))
        spoiled = dict(outputs["rf"]["model"])
        rng = np.random.default_rng([c.seed, 2750159])
        features = np.maximum(spoiled["features_heap"], 0)
        spoiled["thresholds_heap"] = c.thresholds[
            features, rng.integers(0, bins - 1, size=features.shape)
        ]
        out["random_threshold"]["rf_split_gap"] = c.audit_rf(spoiled)["rf_split_gap"]
    if "gb" in classifiers:
        boosted = ref.grow_boosted(binned, cuts, y, bins, depth, rounds, *boost)
        out["half_batch"].update(c.audit_gb(boosted))
        poor = ref.grow_boosted(
            c.bins, c.thresholds, c.y_train, bins, depth, rounds, *boost, allowed=first_only
        )
        out["first_feature"]["gb_split_gap"] = c.audit_gb(poor)["gb_split_gap"]
    return {
        fault: {name: float(value) for name, value in readings.items()}
        for fault, readings in out.items()
    }
