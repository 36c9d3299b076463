"""NetFuse reproduction package: M fine-tuned instances of one
architecture merged into one (M, B) serving grid."""
