"""Tree-ensemble predictors: gradient-boosted trees & random forests
(reference: ``pymoose/pymoose/predictors/tree_ensemble.py``).

TPU-first redesign of the evaluation strategy: the reference emits one
secure ``less`` and one ``mux`` per inner node.  Here a forest, ragged or
complete, compiles to a graph whose size depends on its DEPTH only
(:func:`_compile_forest`, :func:`_forest_scores`):

1. one ``gather`` of every inner node's feature column, (rows, nodes);
2. one ``less`` against the mirrored thresholds: one bit decomposition
   for the whole ensemble;
3. a fold by depth, deepest first.  Nodes whose two children are leaves
   (any depth) are ONE ``mux`` between two mirrored constants, which the
   replicated placement finishes locally once the bit is arithmetic;
   the remaining nodes of a depth are one ``mux`` whose branches are
   gathered from a pool holding the public leaves, the nodes above, and
   the depth below;
4. the trees' roots, gathered from the last pool: (rows, trees).

Same oblivious semantics as the reference (every path of every tree is
evaluated; the gather indices are public tree shape; no data-dependent
control flow); some 50 logical ops for a 100-tree depth-6 forest where
the per-node form needed 25,000.
"""

import abc
import dataclasses

import moose_tpu as pm

from .. import telemetry
from . import predictor
from . import predictor_utils as utils

BRANCH_LT = "BRANCH_LT"  # true branch where x < t (XGBoost; the default)
BRANCH_LEQ = "BRANCH_LEQ"  # true branch where x <= t (sklearn trees)


class DecisionTreeRegressor(predictor.Predictor):
    def __init__(self, weights, children, split_conditions, split_indices,
                 modes=None):
        super().__init__()
        self.weights = weights
        self.left, self.right = children
        self.split_conditions = split_conditions
        self.split_indices = split_indices
        # per node, BRANCH_LT or BRANCH_LEQ (read at inner nodes only)
        self.modes = modes or [BRANCH_LT] * len(self.left)

    @classmethod
    def from_json(cls, tree_json):
        """Build from an XGBoost dump_model(dump_format="json") tree."""
        weights = dict(enumerate(tree_json["base_weights"]))
        left = _map_json_to_onnx_leaves(tree_json["left_children"])
        right = _map_json_to_onnx_leaves(tree_json["right_children"])
        split_conditions = tree_json["split_conditions"]
        split_indices = tree_json["split_indices"]
        return cls(weights, (left, right), split_conditions, split_indices)

    def aes_predictor_factory(self):
        raise NotImplementedError(
            f"{self.__class__.__name__} is not meant to be used directly as "
            "an AesPredictor model. Consider expressing your decision tree "
            "as a tree ensemble with another AesPredictor implementation."
        )

    def is_inner(self, node) -> bool:
        return self.left[node] != 0 and self.right[node] != 0

    def __call__(self, x, n_features, rescale_factor, fixedpoint_dtype):
        del n_features  # shape comes from x; kept for API compatibility
        scores = _forest_scores(
            _compile_forest([self], rescale_factor), x, fixedpoint_dtype,
            self.mirrored,
        )
        return pm.index_axis(scores, axis=1, index=0)


@dataclasses.dataclass(frozen=True)
class _Level:
    """The nodes one ``mux`` evaluates: their columns in the split-bit
    tensor, and what the bit chooses between.  For the nodes whose two
    children are leaves the branches are leaf VALUES; for the others
    they are column INDICES into the pool of that depth."""

    cols: tuple
    when_true: tuple
    when_false: tuple


@dataclasses.dataclass(frozen=True)
class _ForestPlan:
    """A forest as the level fold evaluates it (public tree shape)."""

    features: tuple  # per split-bit column, the feature compared
    thresholds: tuple  # per split-bit column, its threshold
    n_lt: int  # the first n_lt columns are x < t, the others x <= t
    leaves: tuple  # public values that sit in the pool as columns
    local: _Level  # nodes with two leaf children, of any depth
    levels: tuple  # the other nodes by depth, deepest first
    roots: tuple  # per tree, its root's column in the last pool
    depth: int  # inner nodes on the longest root-to-leaf path


def _compile_forest(trees, rescale_factor) -> _ForestPlan:
    """Order a forest's inner nodes for the level fold.

    A node's bit is ``x < t`` as the file writes it, or, for
    ``BRANCH_LEQ``, ``t < x`` with the node's children swapped
    (``x <= t`` is ``not (t < x)``): comparisons stay exact on the
    encoded operands and nothing is negated under sharing.
    """
    nodes = []  # (tree, node, depth) of every reachable inner node
    for t, tree in enumerate(trees):
        stack = [(0, 0)] if tree.is_inner(0) else []
        while stack:
            n, depth = stack.pop()
            nodes.append((t, n, depth))
            for child in (tree.right[n], tree.left[n]):
                if tree.is_inner(child):
                    stack.append((child, depth + 1))
    for t, n, _ in nodes:
        if trees[t].modes[n] not in (BRANCH_LT, BRANCH_LEQ):
            raise ValueError(
                f"tree {t} node {n}: unsupported nodes_modes entry "
                f"{trees[t].modes[n]!r} (supported: {BRANCH_LT}, "
                f"{BRANCH_LEQ})"
            )
    # columns: the x < t nodes, then the x <= t nodes
    nodes.sort(key=lambda tnd: trees[tnd[0]].modes[tnd[1]] == BRANCH_LEQ)
    n_lt = sum(trees[t].modes[n] == BRANCH_LT for t, n, _ in nodes)
    col_of = {(t, n): c for c, (t, n, _) in enumerate(nodes)}

    def branches(t, n):
        tree = trees[t]
        if tree.modes[n] == BRANCH_LEQ:  # the bit is t < x
            return tree.right[n], tree.left[n]
        return tree.left[n], tree.right[n]

    def leaf_value(t, n):
        return rescale_factor * trees[t].weights[n]

    local, by_depth = [], {}
    for t, n, depth in nodes:
        if any(trees[t].is_inner(c) for c in branches(t, n)):
            by_depth.setdefault(depth, []).append((t, n))
        else:
            local.append((t, n))
    depths = sorted(by_depth, reverse=True)  # deepest first

    # the pool of a depth: [public leaves][two-leaf nodes][depth below]
    leaves = []
    slot_of = {}  # (tree, node) -> column in the pool

    def leaf_slot(t, n):
        leaves.append(leaf_value(t, n))
        return len(leaves) - 1

    for d in depths:
        for t, n in by_depth[d]:
            for c in branches(t, n):
                if not trees[t].is_inner(c):
                    slot_of[(t, c)] = leaf_slot(t, c)
    for t, tree in enumerate(trees):
        if not tree.is_inner(0):
            slot_of[(t, 0)] = leaf_slot(t, 0)
    for i, key in enumerate(local):
        slot_of[key] = len(leaves) + i
    below = len(leaves) + len(local)
    levels = []
    for d in depths:
        # this depth's branches read the pool that holds depth d + 1
        when_true, when_false = zip(*(
            tuple(slot_of[(t, c)] for c in branches(t, n))
            for t, n in by_depth[d]
        ))
        levels.append(_Level(
            tuple(col_of[key] for key in by_depth[d]), when_true, when_false,
        ))
        for i, key in enumerate(by_depth[d]):
            slot_of[key] = below + i

    local_true, local_false = [], []
    for t, n in local:
        a, b = branches(t, n)
        local_true.append(leaf_value(t, a))
        local_false.append(leaf_value(t, b))
    return _ForestPlan(
        features=tuple(trees[t].split_indices[n] for t, n, _ in nodes),
        thresholds=tuple(
            float(trees[t].split_conditions[n]) for t, n, _ in nodes
        ),
        n_lt=n_lt,
        leaves=tuple(leaves),
        local=_Level(
            tuple(col_of[key] for key in local),
            tuple(local_true), tuple(local_false),
        ),
        levels=tuple(levels),
        roots=tuple(slot_of[(t, 0)] for t in range(len(trees))),
        depth=1 + max((d for _, _, d in nodes), default=-1),
    )


def _forest_scores(plan: _ForestPlan, x, fixedpoint_dtype, mirrored):
    """The level fold as a graph: every tree's score for every row,
    (rows, trees), from some 5 logical ops a depth."""

    def public(values):
        return predictor.Predictor.fixedpoint_constant(
            list(values), plc=mirrored, dtype=fixedpoint_dtype
        )

    n_local = len(plan.local.cols)
    n_nodes = len(plan.features)
    telemetry.annotate(
        forest_trees=len(plan.roots), forest_nodes=n_nodes,
        forest_levels=plan.depth,
    )

    def one(parts):
        return parts[0] if len(parts) == 1 else pm.concatenate(parts, axis=1)

    bits = []
    if plan.n_lt:
        lt = slice(0, plan.n_lt)
        bits.append(pm.less(
            pm.gather(x, axis=1, indices=plan.features[lt]),
            public(plan.thresholds[lt]),
        ))
    if plan.n_lt < n_nodes:
        leq = slice(plan.n_lt, n_nodes)
        bits.append(pm.less(
            public(plan.thresholds[leq]),
            pm.gather(x, axis=1, indices=plan.features[leq]),
        ))
    bits = one(bits) if bits else None  # a forest of single leaves has none

    base = []
    if plan.leaves:
        # public values as columns of the secret pool: a sharing of
        # zero of the batch's height (x - x is local on every share)
        # plus the mirrored values: no draw, nothing multiplied
        anchor = pm.gather(x, axis=1, indices=(0,) * len(plan.leaves))
        base.append(pm.add(pm.sub(anchor, anchor), public(plan.leaves)))
    if n_local:
        base.append(pm.mux(
            pm.gather(bits, axis=1, indices=plan.local.cols),
            public(plan.local.when_true),
            public(plan.local.when_false),
        ))
    base = pool = one(base)
    for level in plan.levels:
        values = pm.mux(
            pm.gather(bits, axis=1, indices=level.cols),
            pm.gather(pool, axis=1, indices=level.when_true),
            pm.gather(pool, axis=1, indices=level.when_false),
        )
        pool = pm.concatenate([base, values], axis=1)
    return pm.gather(pool, axis=1, indices=plan.roots)


class TreeEnsemble(predictor.Predictor, metaclass=abc.ABCMeta):
    def __init__(self, trees, n_features, base_score, learning_rate):
        super().__init__()
        self.n_features = n_features
        self.trees = trees
        self.base_score = base_score
        self.learning_rate = learning_rate

    @classmethod
    @abc.abstractmethod
    def from_onnx(cls, model_proto):
        pass

    @abc.abstractmethod
    def post_transform(self, tree_scores, fixedpoint_dtype):
        pass

    def predictor_fn(self, x, fixedpoint_dtype):
        """Every tree's score for every row: one (rows, trees) tensor."""
        return _forest_scores(
            _compile_forest(self.trees, self.learning_rate), x,
            fixedpoint_dtype, self.mirrored,
        )

    def _base_score(self, fixedpoint_dtype):
        return self.fixedpoint_constant(
            self.base_score, self.mirrored, dtype=fixedpoint_dtype
        )

    def __call__(self, x, fixedpoint_dtype=utils.DEFAULT_FIXED_DTYPE):
        tree_scores = self.predictor_fn(x, fixedpoint_dtype=fixedpoint_dtype)
        return self.post_transform(
            tree_scores, fixedpoint_dtype=fixedpoint_dtype
        )


class TreeEnsembleClassifier(TreeEnsemble):
    """Classifier over a forest (binary, multiclass via one-vs-rest).

    Args:
        trees: list of :class:`DecisionTreeRegressor`.
        n_features: expected input feature count.
        n_classes: number of output classes.
        base_score: ensemble bias term.
        learning_rate: leaf weight rescale factor.
        transform_output: whether probabilities are derived (sigmoid /
            softmax) from raw scores.
        tree_class_map: tree index -> class index (one-vs-rest bookkeeping).
    """

    def __init__(
        self,
        trees,
        n_features,
        n_classes,
        base_score,
        learning_rate,
        transform_output,
        tree_class_map,
    ):
        super().__init__(trees, n_features, base_score, learning_rate)
        self.n_classes = n_classes
        self.tree_class_map = tree_class_map
        self.transform_output = transform_output

    @classmethod
    def from_onnx(cls, model_proto):
        (
            forest_node,
            (nodes_treeids, left, right, split_conditions, split_indices,
             modes),
            n_trees,
            n_features,
            base_score,
            learning_rate,
        ) = _onnx_base(model_proto, "TreeEnsembleClassifier")

        class_ids = _ints_attr(forest_node, "class_ids")
        class_nodeids = _ints_attr(forest_node, "class_nodeids")
        class_treeids = _ints_attr(forest_node, "class_treeids")
        class_weights = _floats_attr(forest_node, "class_weights")

        classlabels = _classlabels(forest_node)
        n_classes = len(classlabels)

        post_transform = bytes(
            utils.find_attribute_in_node(forest_node, "post_transform").s
        ).decode()

        if post_transform == "NONE" and n_classes > 2:
            # sklearn random forests store ONE tree per ONNX treeid whose
            # leaves carry per-class weight rows; expand to the
            # one-forest-per-class representation used here
            final_class_treeids = [
                class_id + tree_id * n_classes
                for (tree_id, class_id) in zip(class_treeids, class_ids)
            ]
            n_trees = len(set(final_class_treeids))
            if list(nodes_treeids) != sorted(nodes_treeids):
                raise ValueError(
                    "expected nodes_treeids to be sorted in ONNX file"
                )
            sublists = [
                [t for t in nodes_treeids if t == i]
                for i in sorted(set(nodes_treeids))
            ]
            repeated = [
                [n_classes * i + j for _ in sub]
                for j in range(n_classes)
                for i, sub in enumerate(sublists)
            ]
            final_nodes_treeids = [t for group in repeated for t in group]
        else:
            final_class_treeids = class_treeids
            final_nodes_treeids = nodes_treeids

        builders = [_TreeBuilder() for _ in range(n_trees)]
        n_nodes = len(left)
        for i, tree_id in enumerate(final_nodes_treeids):
            # i % n_nodes re-reads the same ONNX node list for each class's
            # copy when trees were duplicated above
            builders[tree_id].add_node(
                left[i % n_nodes], right[i % n_nodes],
                split_indices[i % n_nodes], split_conditions[i % n_nodes],
                modes[i % n_nodes],
            )
        for tree_id, node_id, w in zip(
            final_class_treeids, class_nodeids, class_weights
        ):
            builders[tree_id].set_leaf(node_id, w)

        trees = [b.build() for b in builders]
        tree_class_map = dict(zip(final_class_treeids, class_ids))

        return cls(
            trees,
            n_features,
            n_classes,
            base_score,
            learning_rate,
            transform_output=post_transform != "NONE",
            tree_class_map=tree_class_map,
        )

    def post_transform(self, tree_scores, fixedpoint_dtype):
        if self.n_classes == 2:
            return self._maybe_sigmoid(tree_scores, fixedpoint_dtype)
        logit = self._ovr_logit(
            tree_scores, axis=1, fixedpoint_dtype=fixedpoint_dtype
        )
        if self.transform_output:
            return pm.softmax(logit, axis=1, upmost_index=self.n_classes)
        return logit

    def _maybe_sigmoid(self, tree_scores, fixedpoint_dtype):
        logit = pm.add(
            pm.sum(tree_scores, axis=1), self._base_score(fixedpoint_dtype)
        )
        pos_prob = pm.sigmoid(logit) if self.transform_output else logit
        pos_prob = pm.expand_dims(pos_prob, axis=1)
        one = self.fixedpoint_constant(
            1, plc=self.mirrored, dtype=fixedpoint_dtype
        )
        neg_prob = pm.sub(one, pos_prob)
        return pm.concatenate([neg_prob, pos_prob], axis=1)

    def _ovr_logit(self, tree_scores, axis, fixedpoint_dtype):
        trees_of = [[] for _ in range(self.n_classes)]
        for tree_ix, model_ix in self.tree_class_map.items():
            trees_of[model_ix].append(tree_ix)
        base_score = self._base_score(fixedpoint_dtype)
        ovr_logits = [
            pm.add(
                pm.sum(pm.gather(tree_scores, axis=1, indices=ixs), axis=1),
                base_score,
            )
            for ixs in trees_of
        ]
        return pm.concatenate(
            [pm.expand_dims(ovr, axis=axis) for ovr in ovr_logits],
            axis=axis,
        )


class TreeEnsembleRegressor(TreeEnsemble):
    """Regressor over a forest (GBTs and random forests)."""

    @classmethod
    def from_onnx(cls, model_proto):
        (
            forest_node,
            (nodes_treeids, left, right, split_conditions, split_indices,
             modes),
            n_trees,
            n_features,
            base_score,
            learning_rate,
        ) = _onnx_base(model_proto, "TreeEnsembleRegressor")

        target_nodeids = _ints_attr(forest_node, "target_nodeids")
        target_treeids = _ints_attr(forest_node, "target_treeids")
        target_weights = _floats_attr(forest_node, "target_weights")

        builders = [_TreeBuilder() for _ in range(n_trees)]
        for i, tree_id in enumerate(nodes_treeids):
            builders[tree_id].add_node(
                left[i], right[i], split_indices[i], split_conditions[i],
                modes[i],
            )
        for tree_id, node_id, w in zip(
            target_treeids, target_nodeids, target_weights
        ):
            builders[tree_id].set_leaf(node_id, w)

        trees = [b.build() for b in builders]
        return cls(trees, n_features, base_score, learning_rate)

    def post_transform(self, tree_scores, fixedpoint_dtype):
        return pm.add(
            pm.sum(tree_scores, axis=1), self._base_score(fixedpoint_dtype)
        )


class _TreeBuilder:
    """Accumulates one tree's flat ONNX node arrays and leaf weights,
    then materializes a :class:`DecisionTreeRegressor`."""

    def __init__(self):
        self.left: list = []
        self.right: list = []
        self.split_indices: list = []
        self.split_conditions: list = []
        self.modes: list = []
        self.weights: dict = {}

    def add_node(self, left, right, split_index, split_condition, mode):
        self.left.append(left)
        self.right.append(right)
        self.split_indices.append(split_index)
        self.split_conditions.append(split_condition)
        self.modes.append(mode)

    def set_leaf(self, node_id, weight):
        self.weights[node_id] = weight

    def build(self) -> "DecisionTreeRegressor":
        return DecisionTreeRegressor(
            weights=self.weights,
            children=(self.left, self.right),
            split_conditions=self.split_conditions,
            split_indices=self.split_indices,
            modes=self.modes,
        )


def _map_json_to_onnx_leaves(json_leaves):
    return [0 if child == -1 else child for child in json_leaves]


def _ints_attr(node, name):
    attr = utils.find_attribute_in_node(node, name)
    if attr.type != 7:  # INTS
        raise ValueError(f"{name} must be of type INTS, found other.")
    return list(attr.ints)


def _floats_attr(node, name):
    attr = utils.find_attribute_in_node(node, name)
    if attr.type != 6:  # FLOATS
        raise ValueError(f"{name} must be of type FLOATS, found other.")
    return list(attr.floats)


def _classlabels(node):
    ints = utils.find_attribute_in_node(
        node, "classlabels_int64s", enforce=False
    )
    strings = utils.find_attribute_in_node(
        node, "classlabels_strings", enforce=False
    )
    if ints is not None and len(ints.ints):
        return list(ints.ints)
    if strings is not None and len(strings.strings):
        return list(strings.strings)
    raise ValueError("TreeEnsembleClassifier carries no class labels")


def _node_modes(node, left, right):
    """``nodes_modes`` as the file writes it: ``BRANCH_LT`` and
    ``BRANCH_LEQ`` are evaluated as written, any other mode of an inner
    node is refused by name.  A file without the attribute is read as
    ``BRANCH_LT`` throughout (the reference's reading)."""
    attr = utils.find_attribute_in_node(node, "nodes_modes", enforce=False)
    if attr is None or not len(attr.strings):
        return [BRANCH_LT] * len(left)
    modes = [bytes(m).decode() for m in attr.strings]
    if len(modes) != len(left):
        raise ValueError(
            f"nodes_modes has {len(modes)} entries for {len(left)} nodes"
        )
    for i, mode in enumerate(modes):
        inner = left[i] != 0 and right[i] != 0
        if inner and mode not in (BRANCH_LT, BRANCH_LEQ):
            raise ValueError(
                f"unsupported nodes_modes entry {mode!r} at node {i}: "
                f"supported are {BRANCH_LT} and {BRANCH_LEQ}"
            )
    return modes


def _onnx_base(model_proto, forest_node_name):
    forest_node = utils.find_node_in_model_proto(
        model_proto, forest_node_name, enforce=False
    )
    if forest_node is None:
        raise ValueError(
            "Incompatible ONNX graph provided: graph must contain a "
            f"{forest_node_name} operator."
        )

    nodes_treeids = _ints_attr(forest_node, "nodes_treeids")
    left = _ints_attr(forest_node, "nodes_truenodeids")
    right = _ints_attr(forest_node, "nodes_falsenodeids")
    split_conditions = _floats_attr(forest_node, "nodes_values")
    split_indices = _ints_attr(forest_node, "nodes_featureids")
    modes = _node_modes(forest_node, left, right)

    n_trees = len(set(nodes_treeids))

    n_features = utils.input_n_features(model_proto)

    n_split_indices = len(set(split_indices))
    largest_split_index = max(split_indices)
    if n_split_indices > n_features or largest_split_index >= n_features:
        raise ValueError(
            f"In the ONNX file, the input shape has {n_features} features "
            f"and there are {n_split_indices} distinct split indices with "
            f"the largest index {largest_split_index}. Validate you set "
            "correctly the `initial_types` when converting your model to "
            "ONNX."
        )

    base_score_attr = utils.find_attribute_in_node(
        forest_node, "base_values", enforce=False
    )
    base_score = (
        0.0 if base_score_attr is None else float(base_score_attr.floats[0])
    )

    # ONNX leaf weights are already scaled by the learning rate
    learning_rate = 1.0

    tree_args = (
        nodes_treeids, left, right, split_conditions, split_indices, modes,
    )
    return (
        forest_node,
        tree_args,
        n_trees,
        n_features,
        base_score,
        learning_rate,
    )
