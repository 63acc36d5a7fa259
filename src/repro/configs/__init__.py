"""Paper-scale graph shapes (``qbs_graphs``) for the dry-run cells."""
